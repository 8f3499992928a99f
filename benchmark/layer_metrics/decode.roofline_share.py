"""Share of its memory roofline the decode step reaches, in percent: the
bytes the step NEEDS (weights once, the cached keys and values of the streams
decoding; ``opcount.decode_step_bytes``) over the chip's HBM bandwidth, over
the measured device time of a step. Decode at batch 8 is bound by bytes, not
FLOPs. Rows and context are the means over the traced decode blocks."""

import numpy as np

from benchmark import opcount
from benchmark.run import read_layer_metric


def read(record):
    step_ms = read_layer_metric("decode.step_ms", record)
    lo, hi = record.get("traced") or (None, None)
    if step_ms is None or lo is None:
        return None
    # streams alive in the traced stretch, and their mean context
    rows, context, samples = 0.0, 0.0, np.linspace(lo, hi, 50)
    for r in record["rows"]:
        if len(r["stamps"]) < 2:
            continue
        alive = (samples >= r["stamps"][0]) & (samples <= r["stamps"][-1])
        rows += alive.mean()
        context += alive.mean() * (r["prompt_tokens"] + len(r["stamps"]) / 2)
    if rows <= 0:
        return None
    need = opcount.decode_step_bytes(record["config"], rows, context)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
