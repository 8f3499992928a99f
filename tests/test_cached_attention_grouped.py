"""``cached_attention`` grouped by KV head against the formulation it
replaced, kept here as the plain reference: repeat K and V along the head
axis, widen everything to float32, contract per query head.

The two are the same mathematics (query head ``h`` reads KV head
``h // group``); the grouped form reads K and V once, in the cache's dtype,
with float32 accumulation. A product of two bf16 numbers is exact in
float32, so the results differ by summation order only: float32 inputs agree
to 1e-5 (6e-7 measured), bf16 inputs to one bf16 step of the output.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.models.llama import cached_attention

B, S_MAX, N, D = 4, 32, 8, 16
# per-row lengths: an empty row, a full one (the last slot is the query's
# own for s_new 1), and two in between
CACHE_LEN = np.array([0, S_MAX - 1, 7, 19], np.int32)
# a model that scales its scores by a multiplier of its own, not 1 / sqrt(d)
# (``LlamaConfig.attention_multiplier``: Granite's 0.015625 at head size 64)
MULTIPLIER = 0.11


def repeat_and_widen_attention(q, k_cache, v_cache, cache_len, sm_scale=None):
    """The formulation before the grouped one, verbatim in what it computes."""
    b, s_new, n, d = q.shape
    n_kv = k_cache.shape[2]
    k_cache = jnp.repeat(k_cache, n // n_kv, axis=2)
    v_cache = jnp.repeat(v_cache, n // n_kv, axis=2)
    scores = jnp.einsum("bind,bjnd->bnij", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * (sm_scale or 1.0 / d ** 0.5)
    qpos = cache_len[:, None] + jnp.arange(s_new)[None, :]
    mask = jnp.arange(k_cache.shape[1])[None, None, :] <= qpos[..., None]
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnij,bjnd->bind", probs, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


@pytest.mark.parametrize(
    "q_dtype,kv_dtype",
    [(jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
     # a float32 model over bf16 pages: the cache's values are widened inside
     # the contraction and q keeps its mantissa
     (jnp.float32, jnp.bfloat16)],
    ids=["f32", "bf16", "f32_over_bf16"])
@pytest.mark.parametrize("sm_scale", [None, MULTIPLIER], ids=["rsqrt_d", "multiplier"])
@pytest.mark.parametrize("s_new", [1, 5])
@pytest.mark.parametrize("group", [1, 4, 8], ids=["mha", "gqa4", "mqa"])
def test_grouped_matches_repeat_and_widen(group, s_new, sm_scale, q_dtype, kv_dtype):
    n_kv = N // group
    kq, kk, kv = jax.random.split(jax.random.key(group * 16 + s_new), 3)
    q = jax.random.normal(kq, (B, s_new, N, D), jnp.float32).astype(q_dtype)
    k = jax.random.normal(kk, (B, S_MAX, n_kv, D), jnp.float32).astype(kv_dtype)
    v = jax.random.normal(kv, (B, S_MAX, n_kv, D), jnp.float32).astype(kv_dtype)
    # a chunk at the very end would run past the slab: keep every query's slot
    cache_len = np.minimum(CACHE_LEN, S_MAX - s_new)
    got = cached_attention(q, k, v, jnp.asarray(cache_len), sm_scale=sm_scale)
    want = repeat_and_widen_attention(q, k, v, jnp.asarray(cache_len), sm_scale=sm_scale)
    assert got.shape == want.shape == (B, s_new, N, D)
    assert got.dtype == want.dtype == q_dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    if q_dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    else:
        # one bf16 step: 2**-8 relative to the value's binade; outputs are
        # averages of unit normals, below 4 in magnitude
        step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 2.0 ** -6))) - 7)
        assert np.all(np.abs(got - want) <= step), np.abs(got - want).max()
