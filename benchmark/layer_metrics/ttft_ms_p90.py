"""90th percentile of the time to first token (from ``due_s``), where the
window holds ten samples beyond it. Recorded, not judged: see ``ttft_ms_p50``;
just under the knee the tail swings with the order of arrivals."""

from benchmark import metrics


def read(record):
    return metrics.percentile_with_room(
        [t for t in map(metrics.ttft_ms, metrics.good(record.get("rows", []))) if t is not None], 90)
