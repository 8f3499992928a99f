"""Live rows per touched expert in the fused decode blocks, over the whole
window (``moe_assignments`` / ``moe_experts_touched`` of ``engine.stats``,
PR 26): how many tokens share one read of an expert's weights. None where the
program has no such counter or no expert was touched."""


def read(record):
    stats = record.get("engine_stats") or {}
    touched = stats.get("moe_experts_touched")
    if not touched:
        return None
    return stats["moe_assignments"] / touched
