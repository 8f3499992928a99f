"""Device milliseconds per insert: device time of the paged insert-prefill
programs (XLA module ``jit_insert_fn``) per execution."""

MODULE = "jit_insert_fn"


def read(record):
    trace = record.get("device_trace") or {}
    calls = trace.get("module_calls", {}).get(MODULE)
    return None if not calls else trace["module_s"][MODULE] / calls * 1e3
