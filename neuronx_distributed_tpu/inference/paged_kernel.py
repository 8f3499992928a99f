"""Paged decode-attention Pallas kernel (ISSUE 17 tentpole).

The paged gather path (models/llama.py ``_decode_attention``) gathers K/V
through the block table EVERY decode step: since PR 38 in chunks of whole
pages up to the reach of the step's longest live row (``KVWalk``; before,
all ``(b, max_seq_len)`` slots of the logical slab), and since PR 40 of the
smallest rung of rows (``KVWalk.ladder``) that holds the live ones; the rows that
fill the rung up and the unmapped tail of every shorter row included. This
kernel is
the fused replacement for the single-token decode step: FlashAttention-style
online-softmax tiling (kernels/flash_attn.py idiom) laid over
PagedAttention's physical page layout, consuming the per-slot block
tables DIRECTLY.

Per query row the grid walks that slot's pages only — the block table is
a scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so each
``(batch, page)`` grid step's BlockSpec index_map resolves
``block_table[b, j]`` BEFORE the kernel body runs and the pipeline
fetches exactly one physical page ``(page_size, n_kv, head_dim)`` from
the pool per step — every local kv head of it, because Mosaic tiles the
pool's last two dims ``(n_kv, head_dim)`` and refuses a block that
squeezes the head axis out of them (the first compile for a v5e; an
earlier ``(batch, kv_head, page)`` grid only ever ran interpreted). No
logical slab is ever built:

* block-sparse over the table — pages whose first position lies beyond
  the row's query position are skipped (``@pl.when`` on the running-max
  accumulators; the row's length, not ``max_seq_len``, bounds the work);
* position mask inside the tile — key position ``j*page_size + r`` is
  visible iff ``<= cache_len[b]`` (the gather reference's bottom-aligned
  causal rule), so stale bytes in reused pages contribute exactly-zero
  probability mass, same as the slab's unwritten zeros;
* online-softmax accumulation — running max / sum / weighted-V scratch
  in VMEM carried across the innermost (page) grid axis, flash_attn.py's
  m/l/acc discipline, finalized on the last page.

int8 pages (``page_dtype="int8"``): K/V tiles arrive quantized with
per-(page, kv-head) fp32 scales as sibling pool leaves
(``cached_key_scale``/``cached_value_scale``); the dequant multiply
happens INSIDE the tile right before the QK^T dot — extending
quantization/core.py's "int8 is what HBM holds, the convert fuses into
the consuming matmul" convention from weights to KV pages.

Runs in Pallas interpret mode off-TPU (``kernels/mode.py``), so the
tier-1 exactness matrix (tests/test_paged_kernel.py) drives the REAL
kernel on the CPU mesh; on TPU the same code lowers to Mosaic. GQA never
repeats K/V in HBM: queries reshape to ``(b, n_kv, group, head_dim)`` and
the kernel walks the kv heads of the page it holds, the flash_attn.py
compact-KV argument.

Numerics contract: fp32 pages produce logits within online-softmax
reassociation distance of the gather reference (token STREAMS are
bit-identical on the serving matrix — the oracle the tests pin); int8
pages get the bounded-divergence oracle (max logit delta + greedy-match
rate >= 0.99 on the bench trace).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.kernels import mode

# flash_attn.py's mask value: large-finite so masked lanes never breed NaNs
NEG_INF = -1e30


# Largest double-buffered K+V page footprint Mosaic was shown to accept on
# a v5e (16 MiB of scoped VMEM by default): 2 pools x 2 pipeline buffers x
# (256, 32, 128) bf16. At twice that the compiler answers "Ran out of
# memory in memory space vmem" (tests/test_aot_tpu_compile.py holds the
# shapes that pass).
_PAGE_TILE_VMEM_BYTES = 8 * 1024 * 1024


def paged_kernel_supported(s_new: int, page_size: int, n_heads: int,
                           n_kv_heads: int) -> bool:
    """Static gate for the kernel branch: single-token decode steps only
    (prefill/chunk widths keep the gather+flash path — that is where the
    dense logical view is actually amortized), with an integral GQA
    group. Mirrors ``flash_supported``'s role for the prefill kernel."""
    return (s_new == 1 and page_size >= 1 and n_kv_heads >= 1
            and n_heads % n_kv_heads == 0)


def paged_kernel_refusal(page_size: int, n_heads: int, n_kv_heads: int,
                         head_dim: int, pool_dtype) -> Optional[str]:
    """Why a model with these page dims cannot take the kernel path, or
    None. Asked once, at model construction (``CausalLM``), so a decode
    step never finds out mid-serve and quietly gathers instead."""
    if not paged_kernel_supported(1, page_size, n_heads, n_kv_heads):
        return (f"n_heads {n_heads} is not a multiple of n_kv_heads "
                f"{n_kv_heads}")
    tile = 4 * page_size * n_kv_heads * head_dim * jnp.dtype(pool_dtype).itemsize
    if tile > _PAGE_TILE_VMEM_BYTES:
        return (f"one grid step holds K and V pages of ({page_size}, "
                f"{n_kv_heads}, {head_dim}) {jnp.dtype(pool_dtype).name} "
                f"double-buffered = {tile} bytes of VMEM, over the "
                f"{_PAGE_TILE_VMEM_BYTES} the TPU compiler accepts; use a "
                f"smaller page_size")
    return None


def quantize_kv_pages(w: jax.Array):
    """absmax int8 quantization of fp K/V pages, per (page, kv-head).

    ``w``: (..., page_size, n_kv, head_dim) fp values — one page or a
    batch/window of pages. Returns ``(q int8, scale fp32)`` with the
    scale keepdims-shaped (..., 1, n_kv, 1) so ``q * scale`` dequantizes
    directly and the scale drops into the sibling cache leaves unchanged.
    quantization/core.py's weight conventions lifted to KV: absmax over
    everything a (page, head) scale covers, the 1e-12 floor keeping
    all-zero pages exact (round(0/eps) == 0), symmetric clip to ±127."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=(-3, -1), keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv_pages(q: jax.Array, scale: jax.Array,
                        dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv_pages` (broadcast multiply)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _decode_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, page_size, pages_per_seq,
                   quantized, sm_scale):
    """One (batch row, page) grid step over ALL local kv heads.

    Refs (post scalar-prefetch): ``bt_ref`` (b, pages_per_seq) block
    table and ``cl_ref`` (b,) query positions in SMEM; ``q_ref`` (n_kv,
    group, hd); ``k_ref``/``v_ref`` (page_size, n_kv, hd) — ONE physical
    page, already routed through the block table by the index_map;
    ``ks_ref``/``vs_ref`` (1, n_kv) that page's per-head scales (int8
    pools); ``o_ref`` (n_kv, group, hd). The block spans the whole head
    axis because Mosaic tiles an array's LAST TWO dims — here (n_kv, hd)
    — and refuses a block that squeezes one of them; heads are walked by
    a static loop instead of a grid axis. Scratch carries the online
    softmax across the page axis (TPU grids iterate the innermost axis
    sequentially per core, so VMEM scratch persists — flash_attn.py's
    forward discipline)."""
    bi = pl.program_id(0)
    j = pl.program_id(1)
    n_kv, g, _ = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    qpos = cl_ref[bi]  # this row's query position == its cache length

    # block-sparse skip: a page whose FIRST position exceeds qpos is
    # entirely masked — skip its flops; the accumulators pass through.
    @pl.when(j * page_size <= qpos)
    def _accumulate():
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_size), 1)
        valid = kpos <= qpos
        for h in range(n_kv):
            q = q_ref[h].astype(jnp.float32)                # (g, hd)
            k = k_ref[:, h, :].astype(jnp.float32)          # (ps, hd)
            v = v_ref[:, h, :].astype(jnp.float32)
            if quantized:
                # in-tile dequant: int8 page * per-(page, head) fp32 scale
                k = k * ks_ref[:, h:h + 1]
                v = v * vs_ref[:, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale   # (g, ps)
            s = jnp.where(valid, s, NEG_INF)
            m_prev, l_prev = m_scr[h], l_scr[h]              # (g, 1) each
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # exp under the mask, not of the mask: exp(NEG_INF - m) can be
            # exp(0)=1 when a whole row is masked — zero it explicitly
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == pages_per_seq - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    cache_len: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Fused decode attention straight off the page pool.

    ``q``: (b, 1, n_heads, hd) — the step's queries at absolute position
    ``cache_len[b]`` (the gather reference's bottom-aligned rule: key j
    visible iff ``j <= cache_len[b]``, which includes the token this very
    step wrote). ``k_pages``/``v_pages``: (num_pages, page_size, n_kv,
    hd) physical pool, POST-write. ``block_table``: (b, pages_per_seq)
    int32 logical->physical map. ``cache_len``: (b,) int32. ``k_scale``/
    ``v_scale``: (num_pages, 1, n_kv, 1) fp32 per-(page, head) scales —
    present iff the pool is int8. Returns (b, 1, n_heads, hd) in
    ``q.dtype``."""
    b, s_new, n_q, hd = q.shape
    if s_new != 1:
        raise ValueError(
            f"paged_decode_attention is the single-token decode kernel "
            f"(s_new == 1), got s_new={s_new}")
    num_pages, page_size, n_kv, _ = k_pages.shape
    if n_q % n_kv:
        raise ValueError(f"n_heads {n_q} must be a multiple of "
                         f"n_kv_heads {n_kv}")
    group = n_q // n_kv
    pages_per_seq = block_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("int8 pools carry BOTH k_scale and v_scale")

    # the same GQA grouping as cached_attention's (n_kv, group) view of the
    # query heads: query head h reads kv head h // group.
    q3 = q[:, 0].reshape(b, n_kv, group, hd)
    if quantized:
        ks3 = k_scale.reshape(num_pages, 1, n_kv).astype(jnp.float32)
        vs3 = v_scale.reshape(num_pages, 1, n_kv).astype(jnp.float32)
        scale_idx = lambda bi, j, bt, cl: (bt[bi, j], 0, 0)  # noqa: E731
    else:
        ks3 = vs3 = jnp.ones((1, 1, n_kv), jnp.float32)
        scale_idx = lambda bi, j, bt, cl: (0, 0, 0)  # noqa: E731

    # the paged indirection: the PAGE axis block index comes from the
    # scalar-prefetched table — one whole pool page per grid step
    page_spec = pl.BlockSpec((None, page_size, n_kv, hd),
                             lambda bi, j, bt, cl: (bt[bi, j], 0, 0, 0))
    row_spec = pl.BlockSpec((None, n_kv, group, hd),
                            lambda bi, j, bt, cl: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=[
            row_spec,
            page_spec,
            page_spec,
            pl.BlockSpec((None, 1, n_kv), scale_idx),
            pl.BlockSpec((None, 1, n_kv), scale_idx),
        ],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((n_kv, group, 1), jnp.float32),   # running max
            pltpu.VMEM((n_kv, group, 1), jnp.float32),   # running denominator
            pltpu.VMEM((n_kv, group, hd), jnp.float32),  # weighted-V acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, page_size=page_size,
            pages_per_seq=pages_per_seq, quantized=quantized,
            sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, group, hd), q.dtype),
        interpret=mode.interpret_kernels(),
        name="paged_decode",
    )(block_table.astype(jnp.int32), cache_len.astype(jnp.int32),
      q3, k_pages, v_pages, ks3, vs3)
    return out.reshape(b, 1, n_q, hd)


def reference_paged_attention(q, k_pages, v_pages, block_table, cache_len,
                              *, k_scale=None, v_scale=None, sm_scale=None):
    """XLA gather oracle: materialize the whole ``(b, max_seq_len)`` logical
    view the way ``_decode_attention``'s gather branch does for a prompt (a
    one-token step reads a prefix of it, of its live rows: ``KVWalk``), then run the dense
    ``cached_attention`` math — the bit-exactness reference the kernel
    tests compare against (and the int8 dequant reference)."""
    from neuronx_distributed_tpu.models.llama import cached_attention

    num_pages, ps, n_kv, hd = k_pages.shape
    pages_per_seq = block_table.shape[1]
    s_max = pages_per_seq * ps
    lpos = jnp.arange(s_max)
    page_idx = block_table[:, lpos // ps]                    # (b, S)
    flat = page_idx * ps + (lpos % ps)[None, :]
    kf = k_pages.reshape(num_pages * ps, n_kv, hd)
    vf = v_pages.reshape(num_pages * ps, n_kv, hd)
    k_all, v_all = kf[flat], vf[flat]
    if k_scale is not None:
        ks = k_scale.reshape(num_pages, n_kv)[page_idx]      # (b, S, n_kv)
        vs = v_scale.reshape(num_pages, n_kv)[page_idx]
        k_all = (k_all.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
        v_all = (v_all.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
    return cached_attention(q, k_all, v_all, cache_len, sm_scale=sm_scale)
