"""Device milliseconds per LIVE decode step: device time of the fused decode
program (XLA module ``jit_fused_fn``) in the traced stretch over the steps of
its blocks in which at least one row was live (``decode_steps.traced_decode``:
from the rows' stamps). The dead steps after a block's last row finished are
run and paid for, so their time is in the numerator and they are not in the
denominator: ``decode.step_ms`` x live steps = the module's device time."""

from benchmark import decode_steps


def read(record):
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    return ran["step_s"] * 1e3
