"""Median milliseconds of an optimizer step: host clock between consecutive
loss fetches in the window."""

import numpy as np


def read(record):
    steps = record.get("step_ms")
    return float(np.median(steps)) if steps else None
