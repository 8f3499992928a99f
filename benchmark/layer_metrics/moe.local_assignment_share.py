"""Share of the router's picks that fell on the experts HELD here, in percent,
over the live rows of the window's fused decode blocks:
``100 x moe_assignments / moe_assignments_routed`` (``engine.stats``; the
second counts every top-k pick of a live row, absent experts included).
One in ``router_experts / n_routed_experts`` if the groups are chosen evenly
(12.5 % for 20 of 160); 100 would mean the layer routes over its own experts
only, 0 that it drops what it holds. None where the configuration holds every
expert it routes over, or the program has no such counter."""


def read(record):
    cfg = record.get("config") or {}
    stats = record.get("engine_stats") or {}
    routed = stats.get("moe_assignments_routed")
    if "router_experts" not in cfg or not routed:
        return None
    return 100.0 * stats["moe_assignments"] / routed
