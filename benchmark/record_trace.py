#!/usr/bin/env python3
"""Record the SMALL trace that ``trace_reduce``'s test reads.

    python benchmark/record_trace.py <out_dir>      (on the chip)

A few executions of two named programs - a bf16 matmul chain
(``jit_bm_matmuls``) and, where there are several chips, an all-reduce over
them (``jit_bm_allreduce``) - inside the ``bm:traced_window`` annotation, with
a ``bm:sleep_to_next_arrival`` gap between them so that idle time has a name.
The ``.xplane.pb`` it leaves is copied to ``tests/benchmark/data/``.
"""

import sys
import time
from pathlib import Path


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: this needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def bm_matmuls(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    n = jax.device_count()
    bm_allreduce = jax.jit(jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")) if n > 1 else None
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    v = jnp.ones((n, 1 << 20), jnp.float32)
    bm_matmuls(x).block_until_ready()
    if bm_allreduce is not None:
        bm_allreduce(v).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bm:traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bm:step_block"):
                bm_matmuls(x).block_until_ready()
                if bm_allreduce is not None:
                    bm_allreduce(v).block_until_ready()
            with jax.profiler.TraceAnnotation("bm:sleep_to_next_arrival"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    for f in Path(out_dir).glob("plugins/profile/*/*.xplane.pb"):
        print(f, f.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
