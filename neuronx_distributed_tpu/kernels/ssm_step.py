"""Mamba-2's one-token recurrence, Pallas-TPU: ONE pass over a layer's state.

    S = decay * S_in + (dt x) (x) B        where the row is live, else S_in
    y = S C

``state (R, h, p, n)`` is the WHOLE flat ``ssm_state`` leaf, the rows of every
Mamba layer one after the other, and ``first`` says where this layer's ``b``
rows begin: block ``(i, j)`` of the grid is row ``first + i``, heads
``j * hb .. (j + 1) * hb``. The leaf is aliased to the first output, so a tile
is read, updated, reduced against ``C`` and written over the bytes it was read
from; the rows of other layers are never visited and keep theirs. A slice of
the layer's rows handed in, or an update of the leaf with what comes out,
would each be a copy of the rows (33.5 MB a layer at the published sizes): the
compiler fuses such a slice into its own loops, never into a custom call.

In XLA the same step is two fusions, a reduction that reads ``S_in`` and an
in-place update that reads it again and writes ``S``: the state moves one and
a half times. Here it moves once (PERF.md, PR 45).

The small operands keep the mixer's layouts, so that XLA makes them with the
fusions it made for its own form (compiled for a described v5e the period's
body holds 543 ops, 544 before; ``B`` and ``C`` handed in as ``(b, 1, n)``
turned the layouts of the mixer's split and gate around them and cost 37
more): ``decay`` (spread along ``p``) and ``dt x`` come ``(b, h, p)`` and a
block ``(hb, p)`` of each, ``p`` along the lanes, is turned by the body so that
``p`` runs down the sublanes beside the tile's; ``B`` and ``C`` ``(b, n)`` come
whole and the body takes its row; ``live (b,)`` is a scalar a row, prefetched
beside ``first``. ``y`` leaves ``(hb, p)`` a block. Everything is float32
whatever the leaf's dtype. Off the TPU the same body runs under the Pallas
interpreter (``kernels/mode.py``).

On the v5e, us a layer call at granite-4.0-h-micro's sizes (16 rows of
``(64, 64, 128)`` float32, 67 MB read and written; my chip runs, PR 45). In
the decode block (traced, 11 232 calls): 108.3, where XLA's in-place update
read 104.5 and its reduction 47.9: 620 GB/s, what a pass that reads AND
writes gets of this memory (XLA's own update: 626; 82 us would be the read
rate). Alone, nine calls a program (``_proof/ssm_bench45.py``): XLA's two
fusions 156.9; this body at ``hb`` 16 / 32 / 64 131 / 116.4 / 116.0; the same
grid only copying its tiles 110.7. A body that loops over the tile's heads,
``decay`` a prefetched scalar a head and a head's ``dt x`` a column, read
127-136.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_tpu.kernels import mode

_TILE = 1 << 20     # bytes of state a grid step reads (and writes)


def head_block(h: int, p: int, n: int, itemsize: int = 4) -> int:
    """Heads a grid step takes: the most that divide ``h``, are whole sublane
    tiles (8) and hold ``_TILE`` bytes of state or less, so that a tile in and
    a tile out, each double-buffered, and the body's values stay inside the
    default scoped VMEM (16 MiB on a v5e); all of ``h`` where it fits or nothing
    smaller does. At the published ``(64, 64, 128)`` float32 that is 32 heads (the
    docstring above has the other sizes' readings); the tests' ``(8, 16, 16)``
    is taken whole."""
    fits = [d for d in (*range(8, h, 8), h) if h % d == 0 and d * p * n * itemsize <= _TILE]
    return fits[-1] if fits else h


def _kernel(first_ref, live_ref, state_ref, decay_ref, dtx_ref, b_ref, c_ref, out_ref, y_ref):
    del first_ref                        # the index maps' operand
    i = pl.program_id(0)
    row = pl.ds(i, 1)
    s_in = state_ref[...].astype(jnp.float32)                       # (hb, p, n)
    s = (decay_ref[...][:, :, None] * s_in
         + dtx_ref[...][:, :, None] * b_ref[row, :][None])
    # a row that is not live keeps its state, bit for bit
    s = jnp.where(live_ref[i] != 0, s, s_in)
    out_ref[...] = s.astype(out_ref.dtype)
    y_ref[...] = jnp.sum(s * c_ref[row, :][None], axis=-1)


def ssm_step(state: jax.Array, first: jax.Array, decay: jax.Array, dtx: jax.Array,
             B: jax.Array, C: jax.Array, live: Optional[jax.Array] = None
             ) -> tuple[jax.Array, jax.Array]:
    """``(state, y)``: rows ``first .. first + b`` of ``state (R, h, p, n)``
    stepped once, in place, and ``y (b, h, p)`` float32 of the same pass.
    ``first`` () int32; ``decay (b, h)`` = ``exp(dt A)``, ``dtx (b, h, p)`` =
    ``dt x``, ``B``, ``C`` ``(b, n)``; ``live (b,)`` bool, None for every row.
    The grid is ``(b, h / head_block)``."""
    from jax.experimental.pallas import tpu as pltpu

    R, h, p, n = state.shape
    b = decay.shape[0]
    hb = head_block(h, p, n, state.dtype.itemsize)
    if decay.shape != (b, h) or dtx.shape != (b, h, p) or B.shape != (b, n) or C.shape != (b, n):
        raise ValueError(f"ssm_step: state {state.shape}, decay {decay.shape}, dtx {dtx.shape}, "
                         f"B {B.shape}, C {C.shape}")
    f32 = jnp.float32
    live = jnp.ones((b,), jnp.int32) if live is None else live.astype(jnp.int32)
    tile = pl.BlockSpec((None, hb, p, n), lambda i, j, first, live: (first[0] + i, j, 0, 0))
    heads = pl.BlockSpec((None, hb, p), lambda i, j, first, live: (i, j, 0))
    rows = pl.BlockSpec((b, n), lambda i, j, first, live: (0, 0))
    state, y = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h, p), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[tile, heads, heads, rows, rows],
            out_specs=(tile, heads),
            grid=(b, h // hb),
        ),
        # operands count the prefetched ones: the leaf is the third
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * h * p * n, transcendentals=0,
            bytes_accessed=2 * b * h * p * n * state.dtype.itemsize),
        interpret=mode.interpret_kernels(),
        name="ssm_step",
    )(jnp.asarray(first, jnp.int32).reshape(1), live, state,
      jnp.broadcast_to(decay.astype(f32)[..., None], (b, h, p)), dtx.astype(f32),
      B.astype(f32), C.astype(f32))
    return state, y
