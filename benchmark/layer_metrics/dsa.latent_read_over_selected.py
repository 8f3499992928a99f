"""Latent slots the sparse layers' decode steps READ over the tokens their
indexers CHOSE, a ratio: ``engine.stats`` counter ``dsa_latent_slots_read``
(the rows of each step's rung as far as the walk goes, times the sparse
layers) over ``dsa_tokens_selected`` (``min(reach, index_topk)`` a live row a
sparse layer-step). 1.0 is a read of the chosen alone (a gather); what lies
above it is the extent read under a mask, its chunk rounding and the rung's
rows that are not live. None where the program has no such counters or no
decode step ran."""


def read(record):
    stats = record.get("engine_stats") or {}
    chosen = stats.get("dsa_tokens_selected")
    if not chosen or stats.get("dsa_latent_slots_read") is None:
        return None
    return stats["dsa_latent_slots_read"] / chosen
