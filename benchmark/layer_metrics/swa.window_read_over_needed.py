"""Ring slots the window layers' decode steps READ over the tokens they
NEEDED, a ratio: ``engine.stats`` counter ``kv_window_slots_read`` (the rings
of the rows of each step's rung, whole, times the window layers) over
``kv_window_slots_needed`` (``min(reach, sliding_window)`` a live row a window
layer-step). 1.0 would be a read of the live rows' windows alone; what lies
above it is the ring's slack beyond the window and the rung's rows that are
not live. A window layer that walked its rows' whole length would read 5-9.
None where the program has no such counters or no decode step ran."""


def read(record):
    stats = record.get("engine_stats") or {}
    needed = stats.get("kv_window_slots_needed")
    if not needed or stats.get("kv_window_slots_read") is None:
        return None
    return stats["kv_window_slots_read"] / needed
