"""Milliseconds a request waited inside the engine for a slot: the mean of the
``queued`` spans (submit to the slot claimed, BEFORE the insert) of the
requests submitted in the window. The median is near 0 in every cell (most
requests find a slot in the pass that follows their submit), so the mean; what
a request waited between its due time and its submit is the benchmark loop's
and is not in it. None on a program without the ``phases`` track, whose
``queued`` span ended at the first token."""

from benchmark import phase_spans


def read(record):
    return phase_spans.queue_wait_mean(record)
