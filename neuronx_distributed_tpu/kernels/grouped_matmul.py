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

Tile sizes come from the shapes (:func:`row_tile`, :func:`sub_tile`,
:func:`_tiles`): few rows (decode) are one m tile, so each touched group's
weights stream through once in blocks of megabytes. Many rows (prefill) take
tiles of 512 or 1 024 rows, so that a group's weights stream once per 512 rows
of it or more, and a visit runs its dots over the 64-row sub-tiles that hold a
row of its group, a loop whose bounds are the prefetched offsets: the MXU's
work follows the groups' rows to within a sub-tile, whatever the tile
(:func:`rows_multiplied` counts it). Off the TPU the same body runs under the
Pallas interpreter (``kernels/mode.py``).
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


_SUB = 64      # a multiple of 16; row_tile's docstring has the others' readings


def row_tile(m: int, E: int) -> tuple[int, int]:
    """``(tm, padded m)`` for ``m`` rows in ``E`` groups. Up to 256 rows are
    one tile (a multiple of 16, bf16's sublane tile): every decode step. More
    take 512-row tiles, 1 024 where the groups' mean rows ``m // E`` reach
    that, or one tile where ``m`` is under it: at 256 rows a visit does 256
    FLOP a weight byte, on the v5e's ridge (240), and re-streams its group's
    weights for every 256 rows of it. A larger tile streams them less and
    every visit streams a whole tile of ``lhs``, which the many small groups
    of a short tile pay for (DeepSeek-V2's 20 groups of ~57 rows: 1.52 ms at
    512, 1.78 at 1 024, 2.00 at 2 048).

    One layer's three matmuls on the v5e, ms (gate + up + act, down; bf16,
    three quarters of the tokens real; my chip runs, PR 43, ``tm`` 256 whole
    = PR 29's kernel -> this one):

    ======================  =======  =====  ================  ================
    insert (rows x bucket)  ``m``    tm     256 whole         tm, 64-row subs
    ======================  =======  =====  ================  ================
    Mixtral 8 x 512         8 192    1 024  10.99 + 5.50      8.62 + 4.56
    Mixtral 4 x 512         4 096    512    6.85 + 3.47       5.01 + 2.85
    Mixtral 2 x 512         2 048    512    4.69 + 2.37       3.37 + 1.89
    Mixtral 1 x 512         1 024    512    3.60 + 1.84       2.94 + 1.66
    Mixtral 2 x 128         512      512    3.24 + 1.66       2.61 + 1.48
    Mixtral 1 x 128         256      256    2.83 + 1.44       the same kernel
    OLMoE 8 x 512           32 768   512    1.94 + 1.11       1.66 + 0.98
    OLMoE 2 x 512           8 192    512    1.08 + 0.63       0.85 + 0.49
    OLMoE 1 x 512           4 096    512    0.93 + 0.53       0.80 + 0.45
    OLMoE 1 x 128           1 024    512    0.84 + 0.47       0.79 + 0.43
    DeepSeek-V2 1 x 2048    12 288   512    1.21 + 0.63       0.99 + 0.53
    decode, 8 rows          16-64    = m    1.92, 0.459,      1.92, 0.456,
    (Mixtral, OLMoE, DSV2)                  0.393             0.391
    ======================  =======  =====  ================  ================

    At Mixtral's 8 x 512 (6 144 real rows of 8 192, 8 groups) the parent's 30
    visits multiplied 7 808 rows; 12-13 visits now multiply 6 560 (93.7 % real)
    in 13.2 ms where the rows' FLOPs need 11.0. Whole 256-row sub-tiles read
    15.6 ms there, 128-row 14.3, 32-row 22.1 (with the parent's blocks; 64-row
    14.3); 512-row tiles 13.8 and 2 048-row tiles do not fit VMEM."""
    if m <= 256:
        tm = -(-m // 16) * 16
        return tm, tm
    tm = min(1024 if m // E >= 1024 else 512, -(-m // _SUB) * _SUB)
    return tm, -(-m // tm) * tm


def sub_tile(tm: int) -> int:
    """Rows of a sub-tile of a ``tm``-row tile: a visit multiplies the
    sub-tiles that hold a row of its group. One tile of rows is one."""
    return tm if tm <= 256 else _SUB


def tiles_touched(sizes: jax.Array, ends: jax.Array, tile: int
                  ) -> tuple[jax.Array, jax.Array]:
    """``(first, count)``: the ``tile``-row tile each group's first row is in
    and how many it has a row in, none for a group without rows. ``ends`` is
    the running sum of ``sizes`` along the groups. The visits (``tile`` = tm)
    and the rows multiplied (``tile`` = sub) are both this."""
    first = lax.div(lax.sub(ends, sizes), np.int32(tile))          # rows are >= 0
    count = lax.select(lax.gt(sizes, np.int32(0)),
                       lax.sub(lax.div(lax.add(ends, np.int32(tile - 1)),
                                       np.int32(tile)), first),
                       lax.full_like(sizes, 0))
    return first, count


def rows_multiplied(group_sizes: jax.Array, tm: int) -> jax.Array:
    """Rows the kernel's dots run over for ``group_sizes (..., E)`` at tile
    ``tm``, summed over everything: a sub-tile once for every group with a
    row in it."""
    sub = sub_tile(tm)
    sizes = lax.convert_element_type(group_sizes, jnp.int32)
    ends = lax.cumsum(sizes, axis=sizes.ndim - 1)
    return lax.mul(jnp.sum(tiles_touched(sizes, ends, sub)[1]), np.int32(sub))


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
    # tiles a group touches: from the tile of its first row to that of its last
    first_tile, n_tiles = tiles_touched(sizes, ends, tm)
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


def _tiles(k: int, n: int, stacks: int, itemsize: int, tm: int, sub: int
           ) -> tuple[int, int]:
    """``(tk, tn)`` of the weight blocks, 8 MiB of them a step between the
    ``stacks``; a dimension no multiple of 128 divides is taken whole.

    One tile of rows (decode): ``tn`` the largest multiple of 128 that divides
    ``n`` up to 2048, ``tk`` the largest that divides ``k`` and fits. Blocks
    of megabytes keep the stream of a touched expert's weights near the
    memory's rate; a wide ``tn`` re-reads ``lhs`` less.

    Sub-tiled rows (prefill) are bound by the MXU, and a sub-tile's dot does
    best over the whole ``k``: Mixtral's gate + up at 8 x 512 read 9.50 ms in
    blocks of (1024, 2048), 10.09 in (2048, 1024), 8.55 in (4096, 512), 9.03
    in (4096, 1024), 9.59 in (4096, 256) (my chip runs, PR 43). Every visit
    streams a whole tile of ``lhs`` once per n tile, so ``tk = k`` where that
    is no more than the weights it streams (``tm <= tn x stacks``), and else
    the whole ``n`` and ``lhs`` once a visit: Mixtral's down (``k`` = 14336)
    read 4.84 ms in (2048, 2048), 4.55 in (1024, 4096), 6.4 in (14336, 256).
    Widths whose sums of a whole-``n`` tile would not fit VMEM beside the
    blocks (no cell has them) take the one-tile rule."""
    def largest(dim: int, limit: int) -> int:
        fits = [t for t in range(128, dim + 1, 128) if dim % t == 0 and t <= limit]
        return fits[-1] if fits else dim

    block = (8 << 20) // stacks // itemsize
    if sub < tm:
        tn = largest(n, block // k)
        if tn * k <= block and tm <= tn * stacks:
            return k, tn
        if stacks * tm * n * 4 <= 32 << 20:      # the float32 sums of a tile
            return largest(k, max(128, block // n)), n
    tn = largest(n, 2048)
    return largest(k, max(128, block // tn)), tn


def _kernel(offsets_ref, group_ref, tile_ref, layer_ref, lhs_ref, *refs,
            tm, sub, tiles_k, finish):
    *rhs_refs, out_ref = refs[: len(refs) // 2 + 1]
    acc_refs = refs[len(refs) // 2 + 1:]
    visit = pl.program_id(1)
    ki = pl.program_id(2)
    g = group_ref[visit]
    # the group's rows, counted from the tile's first
    first = offsets_ref[g] - tile_ref[visit] * tm
    last = offsets_ref[g + 1] - tile_ref[visit] * tm

    def work(at, n):
        """One k step of the tile's rows ``[at, at + n)``: a dot per stack
        and, at the last, the group's rows of them out."""
        rows = pl.ds(at, n)

        @pl.when(ki == 0)
        def _init():
            for acc_ref in acc_refs:
                acc_ref[rows, :] = jnp.zeros((n, acc_ref.shape[1]), jnp.float32)

        # operands stay in their storage dtype (bf16 on the MXU), fp32 accumulate
        lhs = lhs_ref[rows, :]
        for rhs_ref, acc_ref in zip(rhs_refs, acc_refs):
            acc_ref[rows, :] += jnp.dot(lhs, rhs_ref[...],
                                        preferred_element_type=jnp.float32)

        @pl.when(ki == tiles_k - 1)
        def _store():
            row = at + jax.lax.broadcasted_iota(
                jnp.int32, (n, out_ref.shape[1]), 0)
            mine = (row >= first) & (row < last)
            # another group's rows of this tile (stored by its own visit) stay
            out_ref[rows, :] = jnp.where(
                mine, finish(*(acc[rows, :] for acc in acc_refs)),
                out_ref[rows, :].astype(jnp.float32)).astype(out_ref.dtype)

    if sub == tm:                # one sub-tile: the whole tile, no branch
        work(0, tm)
    else:                        # the sub-tiles that hold a row of the group
        lax.fori_loop(jnp.maximum(first, 0) // sub,
                      (jnp.minimum(last, tm) + (sub - 1)) // sub,
                      lambda s, _: work(pl.multiple_of(s * sub, sub), sub), None)


@functools.lru_cache(maxsize=None)
def _kernel_for(tm: int, sub: int, tiles_k: int, finish):
    """One function object per kernel variant: ``pallas_call`` keeps the
    traced body by the function's identity, so the programs of a serving
    cell (a dozen and more, one per insert shape) trace it once."""
    return functools.partial(_kernel, tm=tm, sub=sub, tiles_k=tiles_k,
                             finish=finish)


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
    sub = sub_tile(tm)
    tk, tn = _tiles(k, n, len(rhs), itemsize, tm, sub)
    tiles_k = k // tk
    blocks = (2 * (tm * tk * jnp.dtype(lhs.dtype).itemsize
                   + len(rhs) * tk * tn * itemsize
                   + tm * tn * jnp.dtype(out_dtype).itemsize)
              + len(rhs) * tm * tn * 4)
    weights = pl.BlockSpec((None, None, tk, tn),
                           lambda ni, v, ki, offs, grp, tile, lyr:
                           (lyr[0], grp[v], ki, ni))
    return pl.pallas_call(
        _kernel_for(tm, sub, tiles_k, finish),
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
