"""Share of the router's picks that fell on experts that cost nothing
(identity experts), in percent, over the live rows of the window's fused decode
blocks and the real tokens of its inserts: ``100 x (moe_zero_picks +
moe_insert_zero_picks) / (moe_assignments_routed +
moe_insert_assignments_routed)`` (``engine.stats``; the denominators count
every top-k pick, identity and absent experts included). ``zero_expert_num /
(router_experts + zero_expert_num)`` where the router chooses evenly (33.3 % at
256 of 768): that share of a token's expert work is saved. None where the
configuration has no such experts, or the program has no such counters."""


def read(record):
    cfg = record.get("config") or {}
    stats = record.get("engine_stats") or {}
    picks = (stats.get("moe_assignments_routed") or 0) + (stats.get("moe_insert_assignments_routed") or 0)
    if not cfg.get("zero_expert_num") or "moe_zero_picks" not in stats or not picks:
        return None
    return 100.0 * (stats["moe_zero_picks"] + stats.get("moe_insert_zero_picks", 0)) / picks
