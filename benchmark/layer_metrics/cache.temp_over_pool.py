"""Temporaries of the compiled fused decode program over the bytes of the
page pool (``compiled.memory_analysis()``): how many more copies of the pool
the program holds while it runs, beyond the pool it is given."""


def read(record):
    mem, pool = record.get("fused_decode_memory"), record.get("pool")
    if not mem or not pool or not pool["bytes"]:
        return None
    return mem["temp_bytes"] / pool["bytes"]
