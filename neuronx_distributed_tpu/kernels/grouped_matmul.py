"""Grouped matmul, Pallas-TPU: ``out[r] = lhs[r] @ rhs[group of r]`` for rows
sorted by group (the dropless MoE expert matmul; the megablocks ``gmm``, as
``jax.experimental.pallas.ops.tpu.megablox`` also writes it).

``lhs (m, k)`` holds the rows of group 0, then of group 1, ...;
``group_sizes (E,)`` says how many each has; ``rhs (L, E, k, n)`` holds the
groups' weights of ``L`` layers and ``layer`` says which one this call uses:
a model whose layers are a scan keeps its weights stacked, and a slice of the
stack handed to a kernel is a copy of ALL its groups (the compiler fuses such
a slice into its own dots, never into a custom call), so the index map takes
the layer as it takes the group. Rows beyond ``sum(group_sizes)``
belong to no group. The grid is ``(n tiles, visits, k tiles)``: a *visit* is
one (m tile, group) pair that shares a row, listed in row order by
:func:`group_visits` and handed to the kernel as scalar prefetch, so the
index map of the ``rhs`` block names the group. A group without rows is in no
visit and its weights are never read: the bytes moved follow the groups that
have rows, which is what a decode step with a few live rows needs. Two groups
that meet inside one m tile visit it one after the other and each stores only
its own rows (an output block is revisited consecutively, the TPU's rule).

Rows that belong to no group are never stored: they come back as whatever the
buffer held (NaN under the interpreter), so the caller selects them away with
``where`` and never multiplies them by a zero weight.

Tile sizes come from the shapes (:func:`row_tile`, :func:`_tiles`): few rows
(decode) are one m tile, so each touched group's weights stream through once
in blocks of megabytes; many rows (prefill) take 256-row tiles. Off the TPU
the same body runs under the Pallas interpreter (``kernels/mode.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from neuronx_distributed_tpu.kernels import mode


class GroupVisits(NamedTuple):
    """Scalar-prefetch operands of :func:`grouped_matmul`, one set per
    ``(group_sizes, m, tm)``: shared by the matmuls of one expert layer."""

    offsets: jax.Array   # (E + 1,) first row of each group, then the end
    group: jax.Array     # (tiles_m + E - 1,) group of each visit
    tile: jax.Array      # (tiles_m + E - 1,) m tile of each visit
    count: jax.Array     # () visits that exist (the rest is padding)


def row_tile(m: int) -> tuple[int, int]:
    """``(tm, padded m)`` for ``m`` rows. Up to 256 rows are one tile (a
    multiple of 16, bf16's sublane tile). More take 256-row tiles, or 128
    where 256 does not divide ``m`` rounded up to 128: a group that ends
    inside a tile costs a whole tile's work again, so the tile stays well
    under a group's rows (measured on the v5e at Mixtral's 8 x 512 insert,
    ms a layer: 128 -> 20.5, 256 -> 16.7, 512 -> 19.0; OLMoE's: 3.3, 3.4, 4.1)."""
    if m <= 256:
        tm = -(-m // 16) * 16
        return tm, tm
    mp = -(-m // 128) * 128
    return (256 if mp % 256 == 0 else 128), mp


def group_visits(group_sizes: jax.Array, m: int, tm: int) -> GroupVisits:
    """The (m tile, group) pairs that share a row, in row order. ``m`` is a
    multiple of ``tm``; ``sum(group_sizes) <= m``. Some thirty ``lax``
    equations on vectors of E and of the visits (a few dozen to a few
    hundred): a decode step runs this once a layer and every op of it is
    launched, and every serving program traces and lowers it (a ``jnp`` call
    costs about three ``lax`` ones there)."""
    E = group_sizes.shape[0]
    V = m // tm + E - 1
    sizes = lax.convert_element_type(group_sizes, jnp.int32)
    ends = lax.cumsum(sizes, axis=0)
    starts = lax.sub(ends, sizes)
    first_tile = lax.div(starts, np.int32(tm))                     # rows are >= 0
    # tiles a group touches: from the tile of its first row to that of its
    # last; none when it has no row
    n_tiles = lax.select(lax.gt(sizes, np.int32(0)),
                         lax.sub(lax.div(lax.add(ends, np.int32(tm - 1)), np.int32(tm)),
                                 first_tile),
                         lax.full_like(sizes, 0))
    after = lax.cumsum(n_tiles, axis=0)       # visits up to and with a group
    count = lax.index_in_dim(after, E - 1, 0, keepdims=False)
    visit = np.arange(V, dtype=np.int32)
    zeros = lax.full((V, E), 0, jnp.int32)
    group = lax.min(lax.reduce_sum(
        lax.select(lax.le(lax.broadcast_in_dim(after, (V, E), (1,)),
                          lax.broadcast_in_dim(visit, (V, E), (0,))),
                   lax.full_like(zeros, 1), zeros), (1,)), np.int32(E - 1))
    # a group's visits take consecutive tiles from the one its first row is in
    first = lax.sub(first_tile, lax.sub(after, n_tiles))
    tile = lax.add(visit, lax.reduce_sum(
        lax.select(lax.eq(lax.broadcast_in_dim(group, (V, E), (0,)),
                          lax.broadcast_in_dim(np.arange(E, dtype=np.int32), (V, E), (1,))),
                   lax.broadcast_in_dim(first, (V, E), (1,)), zeros), (1,)))
    # padding visits are never run; keep their tile index inside the array
    tile = lax.select(lax.lt(visit, lax.broadcast_in_dim(count, (V,), ())), tile,
                      lax.full((V,), 0, jnp.int32))
    offsets = lax.concatenate([lax.full((1,), 0, jnp.int32), ends], 0)
    return GroupVisits(offsets, group, tile, count)


def _tiles(k: int, n: int, block_bytes: int, itemsize: int) -> tuple[int, int]:
    """``(tk, tn)`` of a weight block: ``tn`` the largest multiple of 128 that
    divides ``n`` up to 2048, ``tk`` the largest that divides ``k`` and keeps
    the block within ``block_bytes`` (a dimension no multiple of 128 divides
    is taken whole). Blocks of megabytes keep the stream of a touched
    expert's weights near the memory's rate; a wide ``tn`` re-reads ``lhs``
    less."""
    def largest(dim: int, limit: int) -> int:
        fits = [t for t in range(128, dim + 1, 128) if dim % t == 0 and t <= limit]
        return fits[-1] if fits else dim

    tn = largest(n, 2048)
    return largest(k, max(128, block_bytes // (tn * itemsize))), tn


def _kernel(offsets_ref, group_ref, tile_ref, layer_ref, lhs_ref, *refs,
            tm, tiles_k, finish):
    *rhs_refs, out_ref = refs[: len(refs) // 2 + 1]
    acc_refs = refs[len(refs) // 2 + 1:]
    visit = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # operands stay in their storage dtype (bf16 on the MXU), fp32 accumulate
    lhs = lhs_ref[...]
    for rhs_ref, acc_ref in zip(rhs_refs, acc_refs):
        acc_ref[...] += jnp.dot(lhs, rhs_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(ki == tiles_k - 1)
    def _store():
        g = group_ref[visit]
        rows = tile_ref[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, out_ref.shape, 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        # another group's rows of this tile (stored by its own visit) stay
        out_ref[...] = jnp.where(mine, finish(*(acc[...] for acc in acc_refs)),
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _kernel_for(tm: int, tiles_k: int, finish):
    """One function object per kernel variant: ``pallas_call`` keeps the
    traced body by the function's identity, so the programs of a serving
    cell (a dozen and more, one per insert shape) trace it once."""
    return functools.partial(_kernel, tm=tm, tiles_k=tiles_k, finish=finish)


def _as_is(acc):
    return acc


def grouped_matmul(lhs: jax.Array, rhs, layer: jax.Array, visits: GroupVisits,
                   tm: int, finish=_as_is, out_dtype=None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``finish(lhs @ rhs[0][layer, group], lhs @ rhs[1][layer, group], ...)``
    row by row: ``lhs (m, k)`` rows sorted by group, ``rhs`` one or more
    stacks ``(L, E, k, n)`` of one shape (a gated MLP's gate and up share
    ``lhs`` and meet in ``finish``, on the float32 sums, so neither product
    is written out; pass a function that lives as long as the module, see
    :func:`_kernel_for`), ``layer`` () int32, ``visits`` from :func:`group_visits`
    at the same ``m`` and ``tm`` (:func:`row_tile`). Returns ``(m, n)``; rows
    of no group are unspecified. ``interpret``: what ``kernels/mode.py`` says,
    for a caller that keeps traces and so has to key them by it."""
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    _, E, k2, n = rhs[0].shape
    if k != k2 or m % tm or any(r.shape != rhs[0].shape for r in rhs):
        raise ValueError(f"grouped_matmul: lhs {lhs.shape}, "
                         f"rhs {[r.shape for r in rhs]}, tm {tm}")
    out_dtype = out_dtype or lhs.dtype
    itemsize = jnp.dtype(rhs[0].dtype).itemsize
    tk, tn = _tiles(k, n, (8 << 20) // len(rhs), itemsize)
    tiles_k = k // tk
    blocks = (2 * (tm * tk * jnp.dtype(lhs.dtype).itemsize
                   + len(rhs) * tk * tn * itemsize
                   + tm * tn * jnp.dtype(out_dtype).itemsize)
              + len(rhs) * tm * tn * 4)
    weights = pl.BlockSpec((None, None, tk, tn),
                           lambda ni, v, ki, offs, grp, tile, lyr:
                           (lyr[0], grp[v], ki, ni))
    return pl.pallas_call(
        _kernel_for(tm, tiles_k, finish),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, offs, grp, tile, lyr:
                             (tile[v], ki)),
                *[weights] * len(rhs),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, v, ki, offs, grp, tile, lyr:
                                   (tile[v], ni)),
            # at least one visit: an empty grid would leave nothing to wait on
            grid=(n // tn, jnp.maximum(visits.count, 1), tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * len(rhs),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, blocks + (8 << 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(rhs), transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + m * n) * itemsize
            + len(rhs) * min(E, visits.group.shape[0]) * k * n * itemsize),
        interpret=mode.interpret_kernels() if interpret is None else interpret,
        name="grouped_matmul",
    )(visits.offsets, visits.group, visits.tile,
      jnp.asarray(layer, jnp.int32).reshape(1), lhs, *rhs)
