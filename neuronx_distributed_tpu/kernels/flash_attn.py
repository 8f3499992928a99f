"""Flash attention, Pallas-TPU, forward + backward with LSE residuals.

Capability-parity with the reference's NKI kernel glue
(``kernels/flash_attn.py`` — ``NKIAttnFunc``:85, ``nki_flash_attn_func``:151,
kernels imported at :19-27) plus the serving-side masked/prefill usage
(``examples/inference/modules/attention/attention_base.py:103-140``), but the
kernels themselves live here (the reference delegates to
``neuronxcc.nki.kernels``; SURVEY §2.2 marks Pallas flash attention as the
real kernel-engineering workload).

Design (flash-attention-2 tiling written for the MXU/VMEM model):

* forward: grid ``(batch*heads, q_blocks, kv_blocks)``, kv innermost. TPU
  grids execute sequentially per core, so VMEM scratch (running max ``m``,
  normalizer ``l``, accumulator ``acc``) carries across the kv iterations of
  one q block; the output and the LSE residual are written at the last kv
  step. Online softmax in fp32 on the VPU; both matmuls take bf16 operands
  on the MXU with fp32 accumulation (``preferred_element_type``).
* backward: recompute-based (no O(S^2) residuals, matching the reference's
  LSE-stash strategy): a ``delta = rowsum(dO*O)`` pre-pass, a dk/dv kernel
  (grid over kv blocks, q innermost) and a dq kernel (grid over q blocks, kv
  innermost), each rebuilding ``p = exp(qk - lse)`` from the stashed LSE.
* masking is POSITION-BASED and unified: every call carries per-token int32
  positions for queries and keys, and key ``j`` attends to query ``i`` iff
  ``kv_pos[j] <= q_pos[i]``. Pure causal is the default (``q_pos = kv_pos =
  iota``); decode/chunked-prefill against a KV cache passes
  ``q_pos = cache_len + iota`` and marks unwritten cache slots with a large
  sentinel; padded prompts mark pad keys with the sentinel and pad query
  rows with ``-1``. Blocks with no valid pair are skipped via a dynamic
  ``pl.when`` predicate (for pure causal this reproduces the static triangle
  skipping exactly — the program_id comparison was already a traced scalar).
* fully-masked query rows produce output 0 and LSE == NEG_INF (the ``l == 0``
  guard), so pad rows never NaN.
* a WINDOW (``flash_attention(window=w)``, forward only) adds a lower bound:
  key ``j`` also needs ``kv_pos[j] > q_pos[i] - w``, the query's own position
  and the ``w - 1`` before it. The block-skip predicate sees it too, so a
  long prompt through a window layer multiplies the band's blocks only.
  ``window=None`` is the program it always was.

Unlike the reference's kernel (seq must be a multiple of 2048,
flash_attn.py:177-179) block sizes adapt down to the sequence length, so any
seq that is a multiple of the block (default 128) works; ``sq != sk`` is
supported (bottom-aligned causal by default, matching the reference's
KV-cache decode semantics).

On non-TPU backends (CPU tests) the same kernels run under the Pallas
interpreter, so unit tests exercise the real kernel code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_tpu.kernels import mode

NEG_INF = -1e30
LANES = 128   # TPU min lane tile; LSE/delta are stored lane-broadcast
INVALID_POS = 2**30  # kv sentinel: never <= any real query position


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, kv_blocks, window=None):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qp = qp_ref[0, :]                               # (block_q,)
    kp = kp_ref[0, :]                               # (block_k,)
    # skip blocks with no valid (query, key) pair
    run = jnp.min(kp) <= jnp.max(qp)
    if window is not None:      # ... and blocks wholly below the window
        run = run & (jnp.max(kp) > jnp.min(qp) - window)

    @pl.when(run)
    def _compute():
        # operands stay in their storage dtype (bf16 on TPU) so the MXU runs
        # at bf16 rate; accumulation is fp32 via preferred_element_type
        q = q_ref[...]                              # (block_q, d)
        k = k_ref[...]                              # (block_k, d)
        v = v_ref[...]                              # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                               # (block_q, block_k) fp32
        valid = kp[None, :] <= qp[:, None]
        if window is not None:
            valid = valid & (kp[None, :] > qp[:, None] - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:]                          # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit mask on p: for fully-masked rows s - m_new == 0, and
        # exp(0) == 1 would corrupt the normalizer
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_scr[:]
        # fully-masked rows (pad queries) have l == 0 -> output 0, LSE NEG_INF
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # LSE stored broadcast across a 128-lane dim (TPU min tile; same
        # layout as the in-tree pallas kernel) so bwd reads a column for free
        lse_ref[...] = jnp.broadcast_to(m_scr[:] + jnp.log(l_safe), lse_ref.shape)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     qp_ref, kp_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                     *, sm_scale, q_blocks, group):
    # grid (b*hk, kv_blocks, group, q_blocks): one dk/dv block accumulates
    # over its GQA group's q heads AND all q blocks in consecutive grid steps
    # (TPU output revisiting must be consecutive)
    g = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((qi == 0) & (g == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qp = qp_ref[0, :]
    kp = kp_ref[0, :]
    run = jnp.min(kp) <= jnp.max(qp)

    @pl.when(run)
    def _compute():
        # bf16 operands on the MXU, fp32 accumulation (see fwd kernel note)
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, :1]
        delta = delta_ref[...][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        valid = kp[None, :] <= qp[:, None]
        # masked entries: exp(s - lse) may overflow for pad rows (lse NEG_INF);
        # the where() selects them away before any use
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)   # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when((qi == q_blocks - 1) & (g == group - 1))
    def _finalize():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   qp_ref, kp_ref, dq_ref, dq_scr, *, sm_scale, kv_blocks):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    qp = qp_ref[0, :]
    kp = kp_ref[0, :]
    run = jnp.min(kp) <= jnp.max(qp)

    @pl.when(run)
    def _compute():
        # bf16 operands on the MXU, fp32 accumulation (see fwd kernel note)
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, :1]
        delta = delta_ref[...][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        valid = kp[None, :] <= qp[:, None]
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# custom-VJP op over flattened (batch*heads, seq, dim) operands
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_bh(q, k, v, qpos, kpos, sm_scale, block_q, block_k,
                        group, num_q_heads):
    """q: (b*h, sq, d); k/v COMPACT: (b*hk, sk, d) with group = h // hk —
    kernels index the shared kv head via the BlockSpec index_map, so GQA
    K/V are never materialized per-q-head in HBM. ``qpos``/``kpos``:
    (b, 1, s) int32 token positions (see module docstring for semantics)."""
    out, _ = _fwd(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group, num_q_heads)
    return out


def _fwd(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group, num_q_heads,
         window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q_blocks = pl.cdiv(sq, block_q)
    kv_blocks = pl.cdiv(sk, block_k)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, kv_blocks=kv_blocks,
                               **({} if window is None else {"window": int(window)}))
    from jax.experimental.pallas import tpu as pltpu

    h = num_q_heads
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b // h, 0, i)),
            pl.BlockSpec((None, 1, block_k), lambda b, i, j: (b // h, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=mode.interpret_kernels(),
        name="flash_fwd" if window is None else "flash_fwd_window",
    )(q, k, v, qpos, kpos)
    return out, lse


def _flash_fwd_vjp(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group, num_q_heads):
    out, lse = _fwd(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group, num_q_heads)
    return out, (q, k, v, qpos, kpos, out, lse)


def _flash_bwd_vjp(sm_scale, block_q, block_k, group, num_q_heads, res, do):
    q, k, v, qpos, kpos, out, lse = res
    # delta pre-pass: rowsum(do * out) — elementwise, let XLA fuse it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))
    dq, dk, dv = flash_block_grads(
        q, k, v, do, lse, delta, qpos, kpos, sm_scale, block_q, block_k,
        group, num_q_heads,
    )
    return dq, dk, dv, None, None


def flash_block_grads(q, k, v, do, lse, delta, qpos, kpos, sm_scale,
                      block_q, block_k, group, num_q_heads):
    """Run the backward kernels for ONE (q-block, kv-block) pairing under
    EXTERNALLY-supplied softmax statistics: ``lse``/``delta`` are
    lane-broadcast ``(b*h, sq, LANES)`` fp32. When they come from this call's
    own forward this is plain flash backward; when they are GLOBAL statistics
    over a larger key set (ring attention: LSE/delta of the full-sequence
    softmax), the returned (dq, dk, dv) are exactly this block's CONTRIBUTION
    to the global gradients — ``p = exp(s - lse_global)`` is the true global
    probability restricted to this block, which is all the flash backward
    recurrence needs. Shapes/layouts as in :func:`_flash_attention_bh`."""
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q_blocks = pl.cdiv(sq, block_q)
    kv_blocks = pl.cdiv(sk, block_k)
    h = num_q_heads

    dkdv_kernel = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, q_blocks=q_blocks, group=group,
    )
    # q row for compact kv row ``bk`` and member ``g`` is bk*group + g
    # (bh = b*h = (b*hk)*group, heads grouped contiguously per kv head)
    hkv = k.shape[0]  # b * hk
    hk = h // group
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid=(hkv, kv_blocks, group, q_blocks),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bk, j, g, i: (bk * group + g, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda bk, j, g, i: (bk, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda bk, j, g, i: (bk, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda bk, j, g, i: (bk * group + g, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda bk, j, g, i: (bk * group + g, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda bk, j, g, i: (bk * group + g, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda bk, j, g, i: (bk // hk, 0, i)),
            pl.BlockSpec((None, 1, block_k), lambda bk, j, g, i: (bk // hk, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bk, j, g, i: (bk, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda bk, j, g, i: (bk, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=mode.interpret_kernels(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta, qpos, kpos)

    dq_kernel = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, kv_blocks=kv_blocks)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b // h, 0, i)),
            pl.BlockSpec((None, 1, block_k), lambda b, i, j: (b // h, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=mode.interpret_kernels(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta, qpos, kpos)
    return dq, dk, dv


_flash_attention_bh.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_window_bh(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group,
                     num_q_heads, window):
    """:func:`_flash_attention_bh` under a window. Forward only: serving
    prompts are its one caller, and the backward kernels know no lower bound."""
    return _fwd(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group,
                num_q_heads, window)[0]


def _window_fwd_vjp(*args):
    raise NotImplementedError(
        "flash_attention(window=...) is forward only: the backward kernels "
        "mask kv_pos <= q_pos alone")


_flash_window_bh.defvjp(_window_fwd_vjp, lambda *a: None)


def flash_block_forward(q, k, v, qpos, kpos, sm_scale, block_q, block_k,
                        group, num_q_heads):
    """Forward kernel WITH its softmax statistics: returns ``(out, lse)``
    where ``lse`` is lane-broadcast ``(b*h, sq, LANES)`` fp32. No VJP — the
    caller (ring attention) owns the backward by combining
    :func:`flash_block_grads` calls under the global statistics. Shapes as
    in :func:`_flash_attention_bh` (flattened, compact GQA K/V)."""
    return _fwd(q, k, v, qpos, kpos, sm_scale, block_q, block_k, group,
                num_q_heads)


def default_attention_blocks(sq: int) -> tuple:
    """(block_q, block_k) defaults: measured fwd+bwd on a v5-lite chip at 7B
    head dims (32 heads x 128, bf16). (1024, 1024) wins at EVERY seq that
    divides it — the r3 re-sweep at b8/s2048 measured fwd+bwd 37.8ms for
    (1024,1024) vs 62.4ms for the old (256,512) default (1.65x), and 58.6 vs
    61.8ms at s8192 vs (512,1024); 2048-wide blocks exceed the 16MB VMEM
    scope at 8k+. Smaller tiers only serve seqs the big blocks don't divide
    (e.g. 1536), where (512,512) beat (256,512) 56.3 vs 62.4ms at 2k."""
    for b in (1024, 512, 256, 128):
        if flash_supported(sq, sq, b, b):
            return min(b, sq), min(b, sq)
    return min(128, sq), min(128, sq)


def default_prefill_blocks(sq: int) -> tuple:
    """(block_q, block_k) for FORWARD-ONLY use (inference prefill). An
    early sequential sweep suggested small q blocks win the fwd kernel; a
    clean INTERLEAVED re-measurement (host-clock drift hitting every config
    equally, b8/s2048/32h/128d) showed (1024,1024) wins fwd-only as well —
    81.5ms vs 104.9ms for (256,512) incl. the constant host roundtrip — so
    prefill shares the fwd+bwd tiers. Kept as a separate hook: fwd-only
    tuning has its own measurement history and may diverge again."""
    return default_attention_blocks(sq)


def flash_supported(sq: int, sk: int, block_q: int, block_k: int) -> bool:
    """True iff the kernel's shape constraints hold (seqs are multiples of
    the clamped block sizes). Call sites that fall back to dense attention
    must use THIS predicate so the constraint lives in one place."""
    return sq % min(block_q, sq) == 0 and sk % min(block_k, sk) == 0


def resolve_positions(b, sq, sk, causal, q_positions, kv_positions):
    """Fill missing position arrays with the defaults (single source of
    truth for default-mask semantics across the kernel, the XLA golden, and
    the sharded dispatch path)."""
    if q_positions is None or kv_positions is None:
        dq_pos, dk_pos = default_positions(b, sq, sk, causal)
        q_positions = dq_pos if q_positions is None else q_positions
        kv_positions = dk_pos if kv_positions is None else kv_positions
    return q_positions, kv_positions


def default_positions(b, sq, sk, causal):
    """Default query/key positions: keys at ``iota(sk)``; causal queries
    bottom-aligned at ``iota(sq) + (sk - sq)`` (for ``sq == sk`` this is the
    standard causal mask; for ``sq < sk`` the reference's KV-cache decode
    semantics), non-causal queries all-visible at ``sk - 1``."""
    kpos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    if causal:
        qpos = jnp.arange(sq, dtype=jnp.int32) + (sk - sq)
    else:
        qpos = jnp.full((sq,), sk - 1, jnp.int32)
    return jnp.broadcast_to(qpos, (b, sq)), kpos


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention over ``(batch, num_heads, seq, head_dim)`` tensors
    (reference ``nki_flash_attn_func``, kernels/flash_attn.py:151 — same
    BHSD convention).

    GQA: ``k``/``v`` may have fewer heads; the kernels index the shared kv
    head through the BlockSpec index_map (``row // group``), so K/V stay at
    their compact size in HBM — no ``jnp.repeat`` materialization.

    Masking: key ``j`` is visible to query ``i`` iff
    ``kv_positions[b, j] <= q_positions[b, i]``. Defaults give (bottom-
    aligned) causal or full visibility per ``causal``. Pass explicit int32
    position arrays ((b, sq) and (b, sk)) for padded prompts (pad keys →
    ``INVALID_POS``, pad query rows → ``-1``) or KV-cache decode
    (``q_positions = cache_len + iota``, unwritten cache slots →
    ``INVALID_POS``). Gradients flow through q/k/v only. ``window``: key
    ``j`` must also lie above ``q_positions[b, i] - window`` (forward only;
    differentiating a windowed call raises).
    """
    b, h, sq, d = q.shape
    hk = k.shape[1]
    if h % hk != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hk}")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    sk = k.shape[2]
    if not flash_supported(sq, sk, block_q, block_k):
        raise ValueError(
            f"seq lengths (q={sq}, kv={sk}) must be multiples of the block sizes "
            f"(block_q={block_q}, block_k={block_k}); pad the sequence or pass "
            f"smaller blocks (edge blocks are not masked)"
        )
    q_positions, kv_positions = resolve_positions(
        b, sq, sk, causal, q_positions, kv_positions
    )
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hk, sk, d)
    vf = v.reshape(b * hk, sk, d)
    qp = q_positions.astype(jnp.int32).reshape(b, 1, sq)
    kp = kv_positions.astype(jnp.int32).reshape(b, 1, sk)
    if window is not None:
        if window < 1:
            raise ValueError(f"window {window}: a query sees itself at least")
        out = _flash_window_bh(qf, kf, vf, qp, kp, float(sm_scale), block_q, block_k,
                               h // hk, h, int(window))
        return out.reshape(b, h, sq, d)
    out = _flash_attention_bh(
        qf, kf, vf, qp, kp, float(sm_scale), block_q, block_k, h // hk, h
    )
    return out.reshape(b, h, sq, d)


def reference_attention(q, k, v, causal=True, sm_scale=None,
                        q_positions=None, kv_positions=None, window=None):
    """Plain-XLA attention, used as the numerical golden in tests (the role
    of the reference's CPU-control modules, SURVEY §4.2). Supports the same
    position-based masking as :func:`flash_attention`."""
    b, h, sq, d = q.shape
    hk = k.shape[1]
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    sk = k.shape[2]
    q_positions, kv_positions = resolve_positions(
        b, sq, sk, causal, q_positions, kv_positions
    )
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    mask = kv_positions[:, None, None, :] <= q_positions[:, None, :, None]
    if window is not None:
        mask = mask & (kv_positions[:, None, None, :]
                       > q_positions[:, None, :, None] - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: softmax over all NEG_INF is uniform garbage — zero it
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
