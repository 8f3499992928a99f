"""Median time to first token: first token's stamp minus the time the request
was DUE, over the requests that did not fail. Recorded, not judged: a request
waits for the running block's end (0 to a whole block, uniformly) before its
insert, and the median of the ~120 such waits of a window differs by 5 % and
more between two runs of the same code (PERF.md, PR 22)."""

from benchmark import metrics


def read(record):
    return metrics.median([t for t in map(metrics.ttft_ms, metrics.good(record.get("rows", [])))
                           if t is not None])
