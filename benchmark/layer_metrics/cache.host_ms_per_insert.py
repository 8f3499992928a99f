"""Host milliseconds of the cache layer an insert: the window's ``cache_plan``
spans (the radix walk, page allocation and LRU eviction of every row, with the
rollback path) and ``cache_commit`` spans (the rows' prompts into the prefix
index) summed, over its ``admission`` spans. Both lie inside the dispatch
lane's ``insert`` span. None on a program without the spans."""

from benchmark import phase_spans


def read(record):
    return phase_spans.cache_host_per_insert(record)
