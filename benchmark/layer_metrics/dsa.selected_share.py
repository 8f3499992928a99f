"""Share of the tokens visible to the live rows of the window's decode steps
that their indexers CHOSE, in percent: ``engine.stats`` counter
``dsa_tokens_selected`` (``min(reach, index_topk)`` a live row a sparse
layer-step) over ``dsa_tokens_visible`` (``reach``). 100 % is a window whose
rows never passed ``index_topk``: no choice was made; the further under it,
the more of what dense attention would read the choice left out. None where
the program has no such counters or no decode step ran."""


def read(record):
    stats = record.get("engine_stats") or {}
    visible = stats.get("dsa_tokens_visible")
    if not visible or stats.get("dsa_tokens_selected") is None:
        return None
    return 100.0 * stats["dsa_tokens_selected"] / visible
