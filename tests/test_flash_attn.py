"""Flash attention kernel tests: the Pallas kernels run under the interpreter
on CPU, so these exercise the real kernel code path (grid, scratch carry,
online softmax, recompute backward) against the XLA golden."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_attn import flash_attention, reference_attention


def _rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype=jnp.float32)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q = _rand((2, 4, 128, 32), 0)
    k = _rand((2, 4, 128, 32), 1)
    v = _rand((2, 4, 128, 32), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_forward_multiblock_long_seq():
    q = _rand((1, 2, 256, 32), 3)
    k = _rand((1, 2, 256, 32), 4)
    v = _rand((1, 2, 256, 32), 5)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_gqa_head_repeat():
    q = _rand((1, 8, 64, 32), 6)
    k = _rand((1, 2, 64, 32), 7)
    v = _rand((1, 2, 64, 32), 8)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)



def _assert_fwd_bwd_parity(q, k, v, label, **attn_kwargs):
    """Forward + q/k/v gradient parity of flash_attention vs the dense
    reference for one (shapes, kwargs) configuration."""
    out = flash_attention(q, k, v, **attn_kwargs)
    ref = reference_attention(q, k, v, causal=attn_kwargs.get("causal", True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4, err_msg=label)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **attn_kwargs) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(
            q, k, v, causal=attn_kwargs.get("causal", True)) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-4,
            err_msg=f"{label}: grad mismatch for {name}",
        )

@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q = _rand((1, 2, 128, 32), 9)
    k = _rand((1, 2, 128, 32), 10)
    v = _rand((1, 2, 128, 32), 11)
    _assert_fwd_bwd_parity(q, k, v, f"square causal={causal}",
                           causal=causal, block_q=64, block_k=64)


@pytest.mark.parametrize("block_q,block_k", [(32, 64), (64, 32)])
def test_asymmetric_blocks_fwd_and_grads(block_q, block_k):
    """Rectangular (block_q != block_k) tiles are a real production shape
    (the CP study measured (512,1024) tiers, PROFILE.md): the grid math,
    scratch carry, and recompute backward must not assume square blocks."""
    q = _rand((1, 2, 128, 32), 30)
    k = _rand((1, 2, 128, 32), 31)
    v = _rand((1, 2, 128, 32), 32)
    _assert_fwd_bwd_parity(q, k, v, f"asymmetric ({block_q},{block_k})",
                           causal=True, block_q=block_q, block_k=block_k)


def test_asymmetric_blocks_gqa_sq_lt_sk():
    """Rectangular tiles x compact GQA K/V x sq<sk (decode-chunk shape) in
    one case, forward AND backward — the composition the per-feature tests
    miss (e.g. a GQA group-indexing slip in the recompute backward that only
    shows when the q-grid and k-grid lengths differ)."""
    q = _rand((1, 4, 64, 32), 33)
    k = _rand((1, 2, 128, 32), 34)
    v = _rand((1, 2, 128, 32), 35)
    _assert_fwd_bwd_parity(q, k, v, "gqa sq<sk asymmetric",
                           causal=True, block_q=32, block_k=64)


def test_bf16_io_fp32_accumulate():
    q = _rand((1, 2, 128, 32), 12).astype(jnp.bfloat16)
    k = _rand((1, 2, 128, 32), 13).astype(jnp.bfloat16)
    v = _rand((1, 2, 128, 32), 14).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(out).astype(np.float32), np.asarray(ref), rtol=3e-2, atol=3e-2
    )


def test_gqa_gradients_compact_kv():
    """dk/dv accumulate over the GQA group via the 4D-grid kernel; compare
    against the repeat-based XLA golden (grads w.r.t. compact K/V)."""
    q = _rand((2, 8, 64, 32), 20)
    k = _rand((2, 2, 64, 32), 21)
    v = _rand((2, 2, 64, 32), 22)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-4,
            err_msg=f"GQA grad mismatch for {name}",
        )


# --- position-based masking (padding, KV-cache decode, sq<sk) --------------

def _masked_golden(q, k, v, qpos, kpos):
    """Dense fp32 golden with the position mask applied by hand."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = kpos[:, None, None, :] <= qpos[:, None, :, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_padded_prompt_mask():
    """Right-padded prompts: pad keys carry INVALID_POS, pad query rows -1;
    real rows match the golden, pad rows are exactly zero."""
    from neuronx_distributed_tpu.kernels.flash_attn import INVALID_POS

    b, h, s, d = 2, 2, 128, 32
    lengths = np.array([96, 50])
    q = _rand((b, h, s, d), 30)
    k = _rand((b, h, s, d), 31)
    v = _rand((b, h, s, d), 32)
    iota = np.arange(s)
    qpos = jnp.asarray(np.where(iota[None] < lengths[:, None], iota[None], -1), jnp.int32)
    kpos = jnp.asarray(np.where(iota[None] < lengths[:, None], iota[None], INVALID_POS), jnp.int32)
    out = flash_attention(q, k, v, block_q=64, block_k=64,
                          q_positions=qpos, kv_positions=kpos)
    ref = _masked_golden(q, k, v, qpos, kpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    for i, L in enumerate(lengths):
        assert np.all(np.asarray(out)[i, :, L:, :] == 0.0), "pad rows must be zero"


def test_decode_chunk_against_cache():
    """sq < sk with per-slot cache offsets (chunked prefill):
    query i of slot b sits at cache_len[b] + i and sees keys j <= that."""
    b, h, d = 2, 2, 32
    s_new, s_max = 64, 256
    cache_len = np.array([100, 7])
    q = _rand((b, h, s_new, d), 33)
    k = _rand((b, h, s_max, d), 34)
    v = _rand((b, h, s_max, d), 35)
    qpos = jnp.asarray(cache_len[:, None] + np.arange(s_new)[None], jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(s_max, dtype=jnp.int32), (b, s_max))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          q_positions=qpos, kv_positions=kpos)
    ref = _masked_golden(q, k, v, qpos, kpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_bottom_aligned_default_sq_lt_sk():
    """causal with sq<sk defaults to bottom-aligned positions (the decode
    convention the old kernel rejected)."""
    q = _rand((1, 2, 64, 32), 36)
    k = _rand((1, 2, 128, 32), 37)
    v = _rand((1, 2, 128, 32), 38)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    qpos = jnp.asarray(np.arange(64)[None] + 64, jnp.int32)
    kpos = jnp.asarray(np.arange(128)[None], jnp.int32)
    ref = _masked_golden(q, k, v, qpos, kpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_masked_gradients():
    """Grads flow through the masked kernel and match the dense golden,
    including zero grads into pad positions."""
    from neuronx_distributed_tpu.kernels.flash_attn import INVALID_POS

    b, h, s, d = 1, 2, 128, 32
    L = 80
    q = _rand((b, h, s, d), 40)
    k = _rand((b, h, s, d), 41)
    v = _rand((b, h, s, d), 42)
    iota = np.arange(s)
    qpos = jnp.asarray(np.where(iota[None] < L, iota[None], -1), jnp.int32)
    kpos = jnp.asarray(np.where(iota[None] < L, iota[None], INVALID_POS), jnp.int32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64,
                                       q_positions=qpos, kv_positions=kpos) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_masked_golden(q, k, v, qpos, kpos) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-4,
            err_msg=f"masked grad mismatch for {name}",
        )
    assert np.all(np.asarray(g_flash[1])[:, :, L:, :] == 0.0), "pad-key grads must be zero"


def test_default_block_selection():
    """Block-default tiers (r3 re-sweep + interleaved correction,
    PROFILE.md): (1024,1024) whenever it divides — for training fwd+bwd AND
    fwd-only prefill (the interleaved re-measurement showed big blocks win
    both); non-dividing seqs fall to smaller tiers through flash_supported
    (single source of truth)."""
    from neuronx_distributed_tpu.kernels.flash_attn import (
        default_attention_blocks,
        default_prefill_blocks,
        flash_supported,
    )

    assert default_attention_blocks(2048) == (1024, 1024)
    assert default_attention_blocks(8192) == (1024, 1024)
    assert default_attention_blocks(1536) == (512, 512)   # 1536 % 1024 != 0
    # seqs <= the tier clamp to themselves (same contract as before)
    assert default_attention_blocks(768) == (768, 768)
    # interleaved re-measurement showed big blocks win fwd-only too:
    # prefill shares the training tiers (default_prefill_blocks docstring)
    assert default_prefill_blocks(2048) == (1024, 1024)
    assert default_prefill_blocks(768) == (768, 768)
    # every returned pair must satisfy the kernel's divisibility predicate
    for s in (256, 512, 768, 1536, 2048, 4096, 8192, 32768):
        bq, bk = default_attention_blocks(s)
        assert flash_supported(s, s, bq, bk), (s, bq, bk)
        bq, bk = default_prefill_blocks(s)
        assert flash_supported(s, s, bq, bk), (s, bq, bk)


def test_decode_config_picks_prefill_blocks(monkeypatch):
    """decode-mode blocks_for routes through default_prefill_blocks (today
    it delegates to the shared tiers, so the dispatch is asserted by
    diverging the hook — a future fwd-only re-tune must land in decode
    configs and ONLY there)."""
    from neuronx_distributed_tpu.kernels import flash_attn as fa
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    train_cfg = LlamaConfig(max_seq_len=2048)
    serve_cfg = LlamaConfig(max_seq_len=2048, decode=True)
    assert train_cfg.blocks_for(2048) == (1024, 1024)
    assert serve_cfg.blocks_for(2048) == (1024, 1024)
    monkeypatch.setattr(fa, "default_prefill_blocks", lambda sq: (256, 512))
    assert serve_cfg.blocks_for(2048) == (256, 512)   # decode follows the hook
    assert train_cfg.blocks_for(2048) == (1024, 1024)  # training does not
