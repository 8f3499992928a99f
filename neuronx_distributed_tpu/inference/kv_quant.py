"""int8 KV pages: the page format ``page_dtype="int8"`` stores, and the
page-wise attention reference that tests compare the serving read with.

K/V pages are quantised absmax per (page, kv head) with fp32 scales as
sibling pool leaves (``cached_key_scale`` / ``cached_value_scale``):
quantization/core.py's "int8 is what HBM holds, the convert fuses into the
consuming matmul" convention lifted from weights to KV pages. The write path
(``models/llama.py::_decode_attention``) dequantises the pages a step
touches, sets the new tokens, and quantises them again; the read dequantises
a chunk at a time with that chunk's scales.

Numerics contract: int8 pages are bounded-divergence, not bit-exact (max
logit delta and greedy-match rate, tests/test_kv_int8_pages.py); fp pages
through :func:`reference_paged_attention` are what the walk
(``models/llama.py::KVWalk``) is held to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def reference_paged_attention(q, k_pages, v_pages, block_table, cache_len,
                              *, k_scale=None, v_scale=None, sm_scale=None):
    """XLA gather oracle: materialize the whole ``(b, max_seq_len)`` logical
    view slot by slot through the block table (``k_pages`` / ``v_pages``
    (pages, page_size, n_kv, hd), int8 with their ``(pages, 1, n_kv, 1)``
    scales), then run the dense ``cached_attention`` math: the reference the
    tests hold a one-token step's read (``KVWalk``: chunks of whole pages, of
    the live rows, as far as the longest reaches) and the int8 dequant to."""
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
