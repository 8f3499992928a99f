"""Share of the positions the inserts' chunked scan ran over that were real
tokens, in percent: ``engine.stats`` counter ``ssm_scan_tokens`` over
``ssm_scan_positions`` (rows x the bucket, rounded up to the scan's chunk).
None where the program has no such counters or no insert ran."""


def read(record):
    stats = record.get("engine_stats") or {}
    positions = stats.get("ssm_scan_positions")
    if not positions or stats.get("ssm_scan_tokens") is None:
        return None
    return 100.0 * stats["ssm_scan_tokens"] / positions
