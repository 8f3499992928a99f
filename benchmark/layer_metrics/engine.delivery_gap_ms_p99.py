"""99th percentile of the gaps between consecutive deliveries of one stream
(``token_ts`` stamps that advanced): the stall a reader sees when another
request's insert, or a slow block, sits between two of its blocks. Recorded,
never judged: a tail of the open loop."""

from benchmark import metrics


def read(record):
    gaps = metrics.delivery_gaps_ms(metrics.good(record.get("rows", [])))
    return metrics.percentile_with_room(gaps, 99)
