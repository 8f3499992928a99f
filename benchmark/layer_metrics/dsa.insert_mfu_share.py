"""Share of the bf16 peak an insert of a model with a lightning indexer
reaches, in percent: the FLOPs the traced stretch's prompts NEED over their
real tokens (``opcount_sparse.insert_flops``: every weight outside the
experts, a product only for the picks that fell on held experts as
``moe_insert_assignments`` counts them, the index scores of the queries past
``index_topk``, attention over the chosen pairs only, one row of logits a
prompt) over the peak, over the device time of the insert programs in that
stretch. None without an indexer in the configuration, its counters, the
routing counter or a traced insert."""

from benchmark import opcount_sparse

MODULE = "jit_insert_fn"


def read(record):
    cfg = opcount_sparse.sparse_config(record)
    stats = record.get("engine_stats") or {}
    busy = (record.get("device_trace") or {}).get("module_s", {}).get(MODULE)
    lo, hi = record.get("traced") or (None, None)
    if (cfg is None or not busy or lo is None or "dsa_tokens_visible" not in stats
            or stats.get("moe_insert_assignments") is None):
        return None
    inserted = [r["prompt_tokens"] for r in record["rows"] if r["stamps"]]
    lens = [r["prompt_tokens"] for r in record["rows"]
            if r["stamps"] and lo <= r["stamps"][0] <= hi]
    if not lens:
        return None
    # held picks a real token, summed over the layers, over the whole window
    picks = min(stats["moe_insert_assignments"] / sum(inserted),
                cfg["n_routed_experts"] * cfg["num_hidden_layers"])
    return (100.0 * opcount_sparse.insert_flops(cfg, lens, picks)
            / record["peaks"]["bf16_flops_per_s"] / busy)
