"""Host milliseconds per ``step_block()`` call that is neither a dispatch nor
a fetch: the benchmark's span around ``step_block`` minus the engine tracer's
dispatch-lane spans (insert, decode, fetch) inside it; the median over the
calls that did work. This is the scheduler's own cost, and the time the
device waits for it in the synchronous loop."""

import numpy as np


def read(record):
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in record.get("host_spans", [])
             if e["lane"][1] == "dispatch"]
    if not spans or not record.get("block_spans"):
        return None
    spans.sort()
    starts = np.asarray([a for a, _ in spans])
    durs = np.asarray([b - a for a, b in spans])
    host = []
    for a, b, worked in record["block_spans"]:
        if not worked:
            continue
        inside = (starts >= a) & (starts < b)
        if inside.any():
            host.append((b - a - durs[inside].sum()) * 1e3)
    return float(np.median(host)) if host else None
