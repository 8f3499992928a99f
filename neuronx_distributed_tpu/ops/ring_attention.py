"""Ring attention: context-parallel attention over the ``cp`` mesh axis.

A TPU-native EXTENSION beyond the reference's capability surface (SURVEY
§2.3: the reference has NO context parallelism — its long-context story is
Megatron-SP + flash attention, validated to 32k). Ring attention removes the
per-chip sequence ceiling: the sequence stays sharded through attention
itself, and K/V shards rotate around the ``cp`` ring (``lax.ppermute`` over
ICI) while each rank folds one block per step into a numerically-stable
streaming softmax (max/sum-corrected accumulation — the flash-attention
recurrence across ranks instead of across tiles).

Two implementations behind one dispatcher (:func:`ring_attention`):

* ``impl="flash"`` (default on causal paths): each ring step runs the
  Pallas flash kernel on (local q, rotating K/V block) — bf16 MXU matmuls,
  no (s, s) score materialization. The forward merges per-block
  ``(out, lse)`` pairs with the streaming-softmax recurrence; the backward
  (ring-level ``jax.custom_vjp``) re-runs the flash backward kernels per
  block under the GLOBAL LSE/delta statistics — each block call yields
  exactly its contribution to the global gradients, dk/dv accumulators ride
  the same ring as their K/V block and arrive home after ``cp`` rotations.
* ``impl="xla"``: plain-jnp fp32 block math (the original formulation) —
  keeps non-causal support and odd shapes; partial-manual over ``{cp}``
  only, so dp/tp stay GSPMD-auto.

Load balance — ``layout``:

* ``"contiguous"``: rank ``r`` holds global positions ``[r*s_loc,
  (r+1)*s_loc)``. Causally correct, but the last rank sees ``cp`` visible
  blocks while rank 0 sees one: SPMD lockstep wall time is the max, ~2x the
  balanced share as cp grows (fully-future blocks are tile-skipped by the
  kernel's position predicate, so they cost only the launch + ppermute).
* ``"zigzag"``: rank ``r`` holds chunks ``r`` and ``2cp-1-r`` of ``2cp``
  global chunks. EVERY (rank, ring-step) pair then carries exactly 2
  visible chunk-pairs (= s_loc^2/2 score work, the causal average), so
  per-rank work equals the SP+flash per-chip share — the standard balanced
  CP schedule. The kernel's masking is position-based, so zigzag costs
  nothing extra: ranks just pass non-contiguous position vectors. Callers
  own the global zigzag permutation of the sequence dim
  (:func:`zigzag_indices`); loss terms are token-permutation-invariant and
  RoPE must use the true (permuted) positions.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.parallel.mesh import CP_AXIS, DP_AXES, TP_AXIS

_NEG = -1e30


def zigzag_indices(seq_len: int, cp: int) -> "jax.Array":
    """Global gather indices realizing the zigzag layout: position ``j`` of
    the PERMUTED sequence holds token ``zigzag_indices[j]`` of the original.
    Rank ``r``'s contiguous cp-shard of the permuted sequence = original
    chunks ``r`` and ``2cp-1-r``. Apply as ``x[:, zigzag_indices(s, cp)]``
    to ids/labels/positions before feeding a zigzag-CP model."""
    if seq_len % (2 * cp):
        raise ValueError(f"seq_len {seq_len} not divisible by 2*cp={2 * cp}")
    c = seq_len // (2 * cp)
    idx = []
    for r in range(cp):
        idx.append(jnp.arange(r * c, (r + 1) * c))
        idx.append(jnp.arange((2 * cp - 1 - r) * c, (2 * cp - r) * c))
    return jnp.concatenate(idx)


def _rank_positions(rank, cp: int, s_loc: int, layout: str):
    """Global token positions held by ``rank`` (traced), shape (s_loc,)."""
    if layout == "contiguous":
        return rank * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
    if layout == "zigzag":
        c = s_loc // 2
        lo = rank * c + jnp.arange(c, dtype=jnp.int32)
        hi = (2 * cp - 1 - rank) * c + jnp.arange(c, dtype=jnp.int32)
        return jnp.concatenate([lo, hi])
    raise ValueError(f"unknown cp layout {layout!r}")


def _block_update(q, kb, vb, q_pos, k_pos, num, den, mx, sm_scale, causal):
    """Fold one K/V block into the streaming-softmax state.
    q (b,h,s,d); kb/vb (b,h,sk,d); num (b,h,s,d) f32; den/mx (b,h,s) f32."""
    scores = jnp.einsum("bhsd,bhkd->bhsk", q.astype(jnp.float32),
                        kb.astype(jnp.float32)) * sm_scale
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]              # (s, sk)
        scores = jnp.where(mask[None, None], scores, _NEG)
        maskf = mask[None, None].astype(jnp.float32)
    else:
        maskf = jnp.ones((), jnp.float32)
    blk_mx = jnp.max(scores, axis=-1)
    new_mx = jnp.maximum(mx, blk_mx)
    # exp(scores - new_mx) <= 1 always (new_mx >= scores); masked entries are
    # zeroed by the multiply, so the -1e30 sentinel never pollutes the sums
    p = jnp.exp(scores - new_mx[..., None]) * maskf
    corr = jnp.exp(mx - new_mx)
    num = num * corr[..., None] + jnp.einsum("bhsk,bhkd->bhsd", p,
                                             vb.astype(jnp.float32))
    den = den * corr + jnp.sum(p, axis=-1)
    return num, den, new_mx


def _ring_attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_chunk: int = 512,
    mesh: Optional[jax.sharding.Mesh] = None,
    layout: str = "contiguous",
) -> jax.Array:
    """Plain-jnp ring attention (see module docstring, ``impl="xla"``)."""
    mesh = mesh or ps.get_mesh()
    cp = mesh.shape[CP_AXIS]
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    # GQA: the ring rotates COMPACT (n_kv) heads — expanding before the ring
    # would multiply every ppermute's ICI bytes by the group factor; heads
    # expand locally right before each block's compute
    rep = q.shape[1] // k.shape[1]

    def local_fn(q, k, v):
        rank = lax.axis_index(CP_AXIS)
        b, h, s_loc, d = q.shape
        q_pos = _rank_positions(rank, cp, s_loc, layout)
        num0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
        den0 = jnp.zeros((b, h, s_loc), jnp.float32)
        mx0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
        perm = [(i, (i + 1) % cp) for i in range(cp)]

        def fold_block(i, kb, vb, num, den, mx):
            """Fold the block currently held (home rank = rank - i)."""
            src = jnp.mod(rank - i, cp)
            k_pos = _rank_positions(src, cp, s_loc, layout)
            kbf = jnp.repeat(kb, rep, axis=1) if rep > 1 else kb
            vbf = jnp.repeat(vb, rep, axis=1) if rep > 1 else vb

            def q_chunk_step(carry_q, j):
                num, den, mx = carry_q
                sl = lambda a: lax.dynamic_slice_in_dim(a, j * q_chunk, q_chunk, 2)  # noqa: E731
                n_j, d_j, m_j = _block_update(
                    sl(q), kbf, vbf,
                    lax.dynamic_slice_in_dim(q_pos, j * q_chunk, q_chunk, 0),
                    k_pos,
                    sl(num), lax.dynamic_slice_in_dim(den, j * q_chunk, q_chunk, 2),
                    lax.dynamic_slice_in_dim(mx, j * q_chunk, q_chunk, 2),
                    sm_scale, causal,
                )
                num = lax.dynamic_update_slice_in_dim(num, n_j, j * q_chunk, 2)
                den = lax.dynamic_update_slice_in_dim(den, d_j, j * q_chunk, 2)
                mx = lax.dynamic_update_slice_in_dim(mx, m_j, j * q_chunk, 2)
                return (num, den, mx), None

            if s_loc > q_chunk and s_loc % q_chunk == 0:
                (num, den, mx), _ = lax.scan(
                    q_chunk_step, (num, den, mx),
                    jnp.arange(s_loc // q_chunk),
                )
            else:
                num, den, mx = _block_update(q, kbf, vbf, q_pos, k_pos,
                                             num, den, mx, sm_scale, causal)
            return num, den, mx

        def ring_step(carry, i):
            kb, vb, num, den, mx = carry
            num, den, mx = fold_block(i, kb, vb, num, den, mx)
            kb = lax.ppermute(kb, CP_AXIS, perm)
            vb = lax.ppermute(vb, CP_AXIS, perm)
            return (kb, vb, num, den, mx), None

        if cp > 1:  # cp-1 rotate-and-fold steps...
            (kb, vb, num, den, mx), _ = lax.scan(
                jax.checkpoint(ring_step), (k, v, num0, den0, mx0),
                jnp.arange(cp - 1),
            )
        else:
            kb, vb, num, den, mx = k, v, num0, den0, mx0
        # ...then fold the final block WITHOUT the (wasted) last rotation
        num, den, mx = jax.checkpoint(
            lambda kb, vb, num, den, mx: fold_block(cp - 1, kb, vb, num, den, mx)
        )(kb, vb, num, den, mx)
        # causal self-attention: the diagonal is always visible, den > 0
        return (num / jnp.maximum(den, 1e-20)[..., None]).astype(q.dtype)

    # partial-manual over {cp}: specs describe ONLY the manual axis — batch
    # and head shardings (dp, tp) remain GSPMD-auto inside the region
    spec = P(None, None, CP_AXIS, None)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={CP_AXIS}, check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# fused implementation: Pallas flash kernel per ring step
# ---------------------------------------------------------------------------

def merge_block(m, se, acc, o_i, lse_i):
    """Fold one normalized flash block result into the streaming-softmax
    state: ``o_i`` (b*h, s, d), ``lse_i`` lane-broadcast (b*h, s, LANES) from
    :func:`flash_block_forward`; state ``m``/``se`` (b*h, s) fp32, ``acc``
    (b*h, s, d) fp32. Fully-future blocks carry ``lse == NEG_INF`` so their
    weight ``exp(lse - m_new)`` is exactly 0."""
    lse_c = lse_i[:, :, 0]
    m_new = jnp.maximum(m, lse_c)
    c_old = jnp.exp(m - m_new)
    c_i = jnp.exp(lse_c - m_new)
    se = se * c_old + c_i
    acc = acc * c_old[..., None] + o_i.astype(jnp.float32) * c_i[..., None]
    return m_new, se, acc


def _ring_flash_local(cp, sm_scale, block_q, block_k, layout, q, k, v):
    """Per-device ring over flash-kernel block calls (full-manual region:
    q (b, h_loc, s_loc, d), compact GQA k/v (b, hk_loc, s_loc, d))."""
    out, _ = _ring_flash_fwd(cp, sm_scale, block_q, block_k, layout, q, k, v)
    return out


def _ring_flash_fwd(cp, sm_scale, block_q, block_k, layout, q, k, v):
    from neuronx_distributed_tpu.kernels.flash_attn import (
        NEG_INF, flash_block_forward,
    )

    b, h, s, d = q.shape
    hk = k.shape[1]
    group = h // hk
    rank = lax.axis_index(CP_AXIS)
    qp = jnp.broadcast_to(_rank_positions(rank, cp, s, layout), (b, 1, s))
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hk, s, d)
    vf = v.reshape(b * hk, s, d)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def fold(i, kb, vb, m, se, acc):
        src = jnp.mod(rank - i, cp)
        kp = jnp.broadcast_to(_rank_positions(src, cp, s, layout), (b, 1, s))
        o_i, lse_i = flash_block_forward(qf, kb, vb, qp, kp, sm_scale,
                                         block_q, block_k, group, h)
        return merge_block(m, se, acc, o_i, lse_i)

    def ring_step(carry, i):
        kb, vb, m, se, acc = carry
        m, se, acc = fold(i, kb, vb, m, se, acc)
        return (lax.ppermute(kb, CP_AXIS, perm),
                lax.ppermute(vb, CP_AXIS, perm), m, se, acc), None

    m0 = jnp.full((b * h, s), NEG_INF, jnp.float32)
    se0 = jnp.zeros((b * h, s), jnp.float32)
    acc0 = jnp.zeros((b * h, s, d), jnp.float32)
    if cp > 1:  # cp-1 rotate-and-fold steps, then fold the last block in place
        (kb, vb, m, se, acc), _ = lax.scan(
            ring_step, (kf, vf, m0, se0, acc0), jnp.arange(cp - 1))
    else:
        kb, vb, m, se, acc = kf, vf, m0, se0, acc0
    m, se, acc = fold(cp - 1, kb, vb, m, se, acc)
    # causal self-attention: the diagonal is always visible, se > 0
    se_safe = jnp.maximum(se, 1e-20)
    out = (acc / se_safe[..., None]).astype(q.dtype).reshape(b, h, s, d)
    lse_global = m + jnp.log(se_safe)              # (b*h, s) fp32
    return out, (q, k, v, out, lse_global)


def _ring_flash_bwd(cp, sm_scale, block_q, block_k, layout, res, do):
    from neuronx_distributed_tpu.kernels.flash_attn import (
        LANES, flash_block_grads,
    )

    q, k, v, out, lse_global = res
    b, h, s, d = q.shape
    hk = k.shape[1]
    group = h // hk
    rank = lax.axis_index(CP_AXIS)
    qp = jnp.broadcast_to(_rank_positions(rank, cp, s, layout), (b, 1, s))
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hk, s, d)
    vf = v.reshape(b * hk, s, d)
    dof = do.reshape(b * h, s, d)
    of = out.reshape(b * h, s, d)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse_global[..., None], (b * h, s, LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (b * h, s, LANES))
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def fold_grads(i, kb, vb, dkb, dvb, dq_acc):
        src = jnp.mod(rank - i, cp)
        kp = jnp.broadcast_to(_rank_positions(src, cp, s, layout), (b, 1, s))
        # global LSE/delta make each block call produce its exact
        # contribution to the global gradients (flash_block_grads docstring)
        dq_i, dk_i, dv_i = flash_block_grads(
            qf, kb, vb, dof, lse_b, delta_b, qp, kp, sm_scale,
            block_q, block_k, group, h)
        return (dkb + dk_i.astype(jnp.float32),
                dvb + dv_i.astype(jnp.float32),
                dq_acc + dq_i.astype(jnp.float32))

    def ring_step(carry, i):
        kb, vb, dkb, dvb, dq_acc = carry
        dkb, dvb, dq_acc = fold_grads(i, kb, vb, dkb, dvb, dq_acc)
        # dk/dv accumulators ride the ring WITH their K/V block: after the
        # full circle of cp rotations they arrive back at their home rank
        rot = lambda x: lax.ppermute(x, CP_AXIS, perm)  # noqa: E731
        return (rot(kb), rot(vb), rot(dkb), rot(dvb), dq_acc), None

    zkv = jnp.zeros((b * hk, s, d), jnp.float32)
    dq0 = jnp.zeros((b * h, s, d), jnp.float32)
    if cp > 1:  # cp-1 rotate-and-fold steps...
        (kb, vb, dkb, dvb, dq_acc), _ = lax.scan(
            ring_step, (kf, vf, zkv, zkv, dq0), jnp.arange(cp - 1))
    else:
        kb, vb, dkb, dvb, dq_acc = kf, vf, zkv, zkv, dq0
    # ...then fold the last block in place and send ONLY dk/dv the final hop
    # home (the k/v rotation would be discarded — one K+V block of ICI saved)
    dkb, dvb, dq_acc = fold_grads(cp - 1, kb, vb, dkb, dvb, dq_acc)
    if cp > 1:
        dkb = lax.ppermute(dkb, CP_AXIS, perm)
        dvb = lax.ppermute(dvb, CP_AXIS, perm)
    return (dq_acc.astype(q.dtype).reshape(b, h, s, d),
            dkb.astype(k.dtype).reshape(b, hk, s, d),
            dvb.astype(v.dtype).reshape(b, hk, s, d))


_ring_flash_local = jax.custom_vjp(_ring_flash_local, nondiff_argnums=(0, 1, 2, 3, 4))
_ring_flash_local.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    layout: str = "contiguous",
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Fused (Pallas) causal ring attention over BHSD tensors whose S dim is
    sharded over ``cp``. Full-manual shard_map: batch over dp, heads over tp,
    seq over cp — the Pallas call is opaque to the SPMD partitioner, so all
    axes must be manual here (same trade as ops/attention.py).

    ``layout`` must state how the caller laid out the sequence dim (same
    contract and default as :func:`ring_attention`): "contiguous" for
    natural order, "zigzag" iff the data was permuted by
    :func:`zigzag_indices` (balanced schedule — prefer it for training)."""
    from neuronx_distributed_tpu.kernels.flash_attn import (
        default_attention_blocks, flash_supported,
    )

    mesh = mesh or ps.get_mesh()
    cp = mesh.shape[CP_AXIS]
    b, hq, seq, d = q.shape
    if seq % cp:
        raise ValueError(f"global seq {seq} not divisible by cp={cp}")
    s_loc = seq // cp
    if layout == "zigzag" and s_loc % 2:
        raise ValueError(f"zigzag needs even per-rank seq, got {s_loc}")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    dbq, dbk = default_attention_blocks(s_loc)
    block_q = block_q or dbq
    block_k = block_k or dbk
    if not flash_supported(s_loc, s_loc, block_q, block_k):
        raise ValueError(
            f"per-rank seq {s_loc} not a multiple of blocks ({block_q}, {block_k})")
    # zigzag chunk boundary must align to k tiles or future-block skipping
    # degrades (correctness is unaffected — masking is per-element)
    local = functools.partial(_ring_flash_local, cp, float(sm_scale),
                              block_q, block_k, layout)
    spec = P(DP_AXES, TP_AXIS, CP_AXIS, None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_chunk: int = 512,
    mesh: Optional[jax.sharding.Mesh] = None,
    impl: Optional[str] = None,
    layout: str = "contiguous",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Context-parallel multi-head attention over BHSD tensors whose S dim
    is sharded over the ``cp`` mesh axis. K/V may carry fewer (GQA) heads.
    Returns the same layout as ``q``.

    ``impl``: "flash" (fused Pallas blocks), "xla" (plain-jnp blocks), or
    None — auto: flash when the path supports it (causal + block-aligned
    shapes), else xla. ``layout``: see module docstring."""
    mesh = mesh or ps.get_mesh()
    cp = mesh.shape[CP_AXIS]
    if impl is None:
        from neuronx_distributed_tpu.kernels.flash_attn import (
            default_attention_blocks, flash_supported,
        )

        s_loc = q.shape[2] // cp
        bq, bk = (block_q or default_attention_blocks(s_loc)[0],
                  block_k or default_attention_blocks(s_loc)[1])
        ok = (causal and q.shape[2] % cp == 0
              and flash_supported(s_loc, s_loc, bq, bk)
              and (layout != "zigzag" or s_loc % 2 == 0))
        impl = "flash" if ok else "xla"
    if impl == "flash":
        if not causal:
            raise ValueError("impl='flash' ring attention is causal-only")
        return ring_flash_attention(q, k, v, sm_scale=sm_scale,
                                    block_q=block_q, block_k=block_k,
                                    layout=layout, mesh=mesh)
    return _ring_attention_xla(q, k, v, causal=causal, sm_scale=sm_scale,
                               q_chunk=q_chunk, mesh=mesh, layout=layout)
