"""Share of the token slots the inserts' stream mixes ran over that were real
tokens, in percent: ``engine.stats`` counter ``mhc_mix_tokens`` (the prompts'
real tokens x the stack's stream mixes) over ``mhc_mix_slots`` (rows x the
bucket of every insert program run, x the same): what is left is four hidden
states read and written a sub-block for a bucket's padding. None where the
program has no such counters or no insert ran."""


def read(record):
    stats = record.get("engine_stats") or {}
    slots = stats.get("mhc_mix_slots")
    if not slots or stats.get("mhc_mix_tokens") is None:
        return None
    return 100.0 * stats["mhc_mix_tokens"] / slots
