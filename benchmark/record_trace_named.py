#!/usr/bin/env python3
"""Record the SMALL NAMED trace that ``trace_parts``' test reads.

    python benchmark/record_trace_named.py <out_dir>      (on one chip)

``record_trace.py``'s sibling (that file may not be edited outside a
benchmark PR): one program, ``jit_bm_named_step``, that holds what the
reduction by name tells apart - a two-layer flax model with ``attention`` and
``mlp`` modules under ``nn.scan``, a ``jax.named_scope`` (``loss``) around
what flax does not name, a ``value_and_grad`` (so that the backward pass's
names, ``transpose(jvp(...))``, are there) and a ``pallas_call`` with a
``name`` (``bm_double``) - executed twice inside ``bm:traced_window``. The
``.xplane.pb`` it leaves is copied, gzipped, to ``tests/benchmark/data/``.
"""

import sys
from pathlib import Path


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from jax.experimental import pallas as pl

    if jax.devices()[0].platform != "tpu":
        print("record_trace_named: this needs a TPU", file=sys.stderr)
        return 2
    width, seq, heads = 512, 256, 4

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x):
            b, s, h = x.shape
            q, k, v = jnp.split(nn.Dense(3 * h, use_bias=False, dtype=x.dtype, name="qkv")(x), 3, axis=-1)
            q, k, v = (t.reshape(b, s, heads, h // heads) for t in (q, k, v))
            p = jax.nn.softmax(jnp.einsum("bqnd,bknd->bnqk", q, k) / (h // heads) ** 0.5, axis=-1)
            o = jnp.einsum("bnqk,bknd->bqnd", p, v).reshape(b, s, h)
            return nn.Dense(h, use_bias=False, dtype=x.dtype, name="o_proj")(o)

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            up = nn.Dense(4 * width, use_bias=False, dtype=x.dtype, name="up")(x)
            return nn.Dense(width, use_bias=False, dtype=x.dtype, name="down")(jax.nn.gelu(up))

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x, _):
            x = x + Attention(name="attention")(x)
            return x + MLP(name="mlp")(x), None

    class Model(nn.Module):
        @nn.compact
        def __call__(self, x):
            layers = nn.scan(Block, variable_axes={"params": 0}, split_rngs={"params": True},
                             length=2)(name="layers")
            x, _ = layers(x, None)
            with jax.named_scope("loss"):
                return jnp.mean(jnp.square(x.astype(jnp.float32)))

    def double(x):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        flat = x.reshape(-1, x.shape[-1])
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
                              name="bm_double")(flat).reshape(x.shape)

    model = Model()
    x = jnp.ones((2, seq, width), jnp.bfloat16)
    params = model.init(jax.random.key(0), x)

    @jax.jit
    def bm_named_step(params, x):
        return jax.value_and_grad(lambda p: model.apply(p, double(x)))(params)

    jax.block_until_ready(bm_named_step(params, x))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0          # the Python call tracer alone would be megabytes
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bm:traced_window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bm:step_block"):
                jax.block_until_ready(bm_named_step(params, x))
    jax.profiler.stop_trace()
    for f in Path(out_dir).glob("plugins/profile/*/*.xplane.pb"):
        print(f, f.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
