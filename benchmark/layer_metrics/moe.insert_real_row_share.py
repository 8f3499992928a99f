"""Share of the rows the paged inserts' expert layers gathered, multiplied
and combined that were real picks of real tokens, in percent, whole window:
``100 x moe_insert_assignments / moe_insert_rows`` (``engine.stats``). A
layer that holds a share of a wider router's experts sorts every pick and,
on a program that hands them all on, reads one in ``router_experts / experts
held`` (12.5 % at an eighth held); a program that hands on only the picks
that fell on its experts, a bound of rows a pass, reads the expected share of
that bound (50 % at twice the expectation) and less where a call overflowed
into further passes. None where the configuration holds every expert it
routes over, or the program has no such counters."""


def read(record):
    cfg = record.get("config") or {}
    stats = record.get("engine_stats") or {}
    rows = stats.get("moe_insert_rows")
    if "router_experts" not in cfg or not rows or "moe_insert_assignments" not in stats:
        return None
    return 100.0 * stats["moe_insert_assignments"] / rows
