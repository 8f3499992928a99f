"""Device milliseconds per LIVE decode step of a model with Mamba layers: the
fused decode program's device time in the traced stretch over its live steps
(``decode_steps.traced_decode``, as ``decode.step_ms`` reads it). None for a
configuration without such layers."""

from benchmark import decode_steps, opcount_hybrid


def read(record):
    if opcount_hybrid.hybrid_config(record) is None:
        return None
    ran = decode_steps.traced_decode(record)
    return None if ran is None else ran["step_s"] * 1e3
