"""Device milliseconds per decode step: device time of the fused decode
program (XLA module ``jit_fused_fn``) per execution, over its block_steps."""

MODULE = "jit_fused_fn"


def read(record):
    trace = record.get("device_trace") or {}
    calls = trace.get("module_calls", {}).get(MODULE)
    if not calls:
        return None
    return trace["module_s"][MODULE] / calls / record["engine"]["block_steps"] * 1e3
