"""Metric arithmetic of the benchmark: from per-request rows to numbers.

A serving row is ``{"due": s, "submitted": s, "stamps": [s, ...], "want": n,
"prompt_tokens": n, "failed": bool}`` on ONE clock (``time.perf_counter``
minus the window's opening). ``stamps`` are the engine's ``Completion.
token_ts``: the wall stamp of the fetch that delivered each token, so tokens
of one fused block share a stamp.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def percentile_with_room(xs: Sequence[float], q: float, beyond: int = 10) -> Optional[float]:
    """The q-th percentile, only where at least ``beyond`` samples lie beyond
    it (a 90th percentile wants 100 samples); else None."""
    xs = np.asarray(xs, np.float64)
    if xs.size == 0 or xs.size * (100.0 - q) / 100.0 + 1e-9 < beyond:
        return None
    return float(np.percentile(xs, q))


def median(xs: Sequence[float]) -> Optional[float]:
    xs = np.asarray(xs, np.float64)
    return float(np.median(xs)) if xs.size else None


def ttft_ms(row: dict) -> Optional[float]:
    """First token's stamp minus the time the request was DUE (not the time
    it was submitted: a stall that delays submission is the system's)."""
    return None if not row["stamps"] else (row["stamps"][0] - row["due"]) * 1e3


def tpot_ms(row: dict) -> Optional[float]:
    """(last stamp - first stamp) / (tokens - 1): what a reader of the stream
    feels, stalls from other requests' inserts included."""
    n = len(row["stamps"])
    return None if n < 2 else (row["stamps"][-1] - row["stamps"][0]) / (n - 1) * 1e3


def delivery_gaps_ms(rows: List[dict]) -> np.ndarray:
    """Gaps between consecutive DELIVERIES of one stream (stamp advanced)."""
    gaps = []
    for r in rows:
        s = np.unique(np.asarray(r["stamps"], np.float64))
        gaps.extend(np.diff(s) * 1e3)
    return np.asarray(gaps, np.float64)


def good(rows: List[dict]) -> List[dict]:
    return [r for r in rows if not r["failed"]]


def slo_attainment(rows: List[dict], limits: dict) -> Optional[float]:
    """Share of ATTEMPTED requests that met both limits; a failed request
    misses every limit."""
    if not rows or not limits:
        return None
    met = 0
    for r in rows:
        if r["failed"]:
            continue
        t, p = ttft_ms(r), tpot_ms(r)
        if t is not None and t <= limits["ttft_ms"] and (p is None or p <= limits["tpot_ms"]):
            met += 1
    return met / len(rows)


def serving_end_to_end(rows: List[dict], seconds: float) -> dict:
    """Every serving end-to-end metric this benchmark knows, by name; the
    harness prints those the cell lists. None where the sample is too small."""
    ok = good(rows)
    ttft = [t for t in map(ttft_ms, ok) if t is not None]
    tpot = [t for t in map(tpot_ms, ok) if t is not None]
    done_in_window = [r for r in ok if r["stamps"] and r["stamps"][-1] <= seconds]
    # the rate over whole completed groups: up to the last completion inside
    # the window, so that a group cut by the window's edge does not quantise it
    span = max((r["stamps"][-1] for r in done_in_window), default=0.0)
    return {
        "ttft_ms_p50": median(ttft),
        "ttft_ms_p90": percentile_with_room(ttft, 90),
        "tpot_ms_p50": median(tpot),
        # closed-loop scoring: prompt tokens of the requests completed inside
        # the window, per second of window
        "tokens_per_s": (sum(r["prompt_tokens"] for r in done_in_window) / span
                         if span > 0 else None),
    }
