"""Pages in use at the peak over the pages of the pool, in percent (the
pool's own counter; cached prefixes of finished requests count as in use)."""


def read(record):
    pool = record.get("pool")
    return None if not pool else 100.0 * pool["pages_in_use_peak"] / pool["pages"]
