"""Share of the window's inserts that the scheduler dispatched while an
earlier insert's first tokens were still on the device, in percent:
``100 x inserts_overlapped / inserts`` (``engine.stats``). Such an insert was
planned, built and dispatched on the host while the device ran the one before
it, so the device found it queued when that one ended. A closed loop of
one-token requests with more callers than slots reads near 100 (every insert
but the first); a program that fetches every insert where it dispatches it
reads 0. None where the program has no such counter."""


def read(record):
    stats = record.get("engine_stats") or {}
    inserts = stats.get("inserts")
    if not inserts or "inserts_overlapped" not in stats:
        return None
    return 100.0 * stats["inserts_overlapped"] / inserts
