"""The one place that decides how the Pallas kernels run.

On a TPU they are compiled by Mosaic; everywhere else the same kernel
bodies run under the Pallas interpreter, which is how the CPU tests drive
the real kernel code. Nothing else in the package asks the backend this
question, so a run that was meant for the chip and landed on the host is
visible in one spot: ``chip_smoke.py`` refuses to start off-TPU and looks
for ``tpu_custom_call`` in the compiled programs, and the ahead-of-time
compile tests (tests/test_aot_tpu_compile.py), which lower for a described
TPU from a CPU process, patch ``interpret_kernels`` and nothing else.
"""

import jax


def interpret_kernels() -> bool:
    return jax.default_backend() != "tpu"


def flash_where_compiled(say) -> bool:
    """``use_flash_attention`` for an entry point whose model carries no
    attention choice of its own (an HF checkpoint, the compile check): the
    flash kernel where Mosaic compiles it, dense XLA attention where it
    would run interpreted. ``say`` is told which, so a run that was meant
    for the chip and landed on the host shows."""
    interpreted = interpret_kernels()
    say(f"attention on backend {jax.default_backend()}: "
        + ("dense XLA (the Pallas flash kernel would run interpreted)"
           if interpreted else "Pallas flash kernel"))
    return not interpreted
