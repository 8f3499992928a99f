"""Share of ATTEMPTED requests that met both limits of the traffic file (time
to first token and time per output token), in percent; a failed request
misses both. Recorded, never judged."""

from benchmark import metrics


def read(record):
    share = metrics.slo_attainment(record.get("rows", []), record["mix"].get("limits"))
    return None if share is None else 100.0 * share
