"""Share of the expert slots (layer steps x experts held) that the live rows of
the fused decode blocks chose, over the whole window: what a read of only the
chosen experts would have needed of the expert weights the program reads.
From ``engine.stats`` (``moe_experts_touched`` / ``moe_layer_steps``, PR 26);
None where the program has no such counter or the model no experts."""


def read(record):
    stats = record.get("engine_stats") or {}
    experts = (record.get("config") or {}).get("num_experts")
    layer_steps = stats.get("moe_layer_steps")
    if not experts or not layer_steps:
        return None
    return 100.0 * stats["moe_experts_touched"] / (layer_steps * experts)
