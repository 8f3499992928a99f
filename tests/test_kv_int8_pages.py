"""int8 KV pages (``page_dtype="int8"``, ``inference/kv_quant.py``).

Bounded divergence, not bit-exactness: per-page quantize/dequantize
round-trip units (absmax edge cases), insert-logit max-delta bound,
greedy-token-match vs the fp32 oracle, pool bytes <= 0.55x fp32 at equal page
count, and the crc32/repair seam catching a garbled int8 page before it is
ever decoded. (The read itself, int8 pages included, is held to
``reference_paged_attention`` in ``tests/test_decode_extent.py``.)

Tier-1 cost discipline: one module-scoped param set behind both lms
(test_paged_cache's tiny dims, block_steps=K shared).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    DisaggRouter,
    FaultPlan,
    Sampler,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.kv_quant import (
    dequantize_kv_pages,
    quantize_kv_pages,
)
from neuronx_distributed_tpu.inference.replay import run_trace
from neuronx_distributed_tpu.inference.partition import leaf_partition_spec
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4


@pytest.fixture(scope="module")
def stack():
    """(fp32-page lm, int8-page lm) over ONE weight set: the first is the
    second's oracle."""
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)

    def mk(**kw):
        return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                        max_batch=3, page_size=PAGE, **kw).compile()

    return mk(), mk(page_dtype="int8")


def _prompts(n, s=8, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _mixed_submits(seed=5):
    p = _prompts(3, seed=seed)
    return [dict(prompt=p[0], max_new_tokens=12),
            dict(prompt=p[1], max_new_tokens=8, arrival_block=1,
                 sampler=Sampler(temperature=1.3)),
            dict(prompt=p[2], max_new_tokens=10, arrival_block=1,
                 sampler=Sampler(temperature=0.8))]


def _streams(obj):
    return {c.request_id: c.tokens.tolist() for c in obj.completed}


def _serve(lm, submits, **eng_kw):
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42), **eng_kw)
    for kw in submits:
        eng.submit(**kw)
    eng.run(max_blocks=300)
    return eng


# ------------------------------------------------- quantize round-trip units

def test_quantize_roundtrip_all_zero_page():
    """The absmax floor keeps an all-zero page EXACT (0/eps rounds to 0)
    — no spurious DC offset on unwritten pages."""
    w = jnp.zeros((PAGE, 2, 8), jnp.float32)
    q, s = quantize_kv_pages(w)
    assert q.dtype == jnp.int8 and s.shape == (1, 2, 1)
    assert np.all(np.asarray(q) == 0)
    np.testing.assert_array_equal(np.asarray(dequantize_kv_pages(q, s)), 0.0)


def test_quantize_roundtrip_single_outlier_token():
    """One huge token stretches its (page, head) scale: the outlier
    round-trips near-exactly and every other element's error stays within
    the half-step bound scale/2 (the absmax contract — degraded
    resolution, never a wrong magnitude)."""
    w = 0.01 * jax.random.normal(jax.random.key(8), (PAGE, 2, 8))
    w = w.at[1, 0, 3].set(50.0)
    q, s = quantize_kv_pages(w)
    dq = dequantize_kv_pages(q, s)
    err = np.abs(np.asarray(dq) - np.asarray(w))
    assert np.asarray(s)[0, 0, 0] == pytest.approx(50.0 / 127.0)
    assert err.max() <= np.asarray(s).max() / 2 + 1e-7
    assert np.asarray(dq)[1, 0, 3] == pytest.approx(50.0, rel=1e-2)
    # the outlier-free head kept its own tight scale
    assert np.asarray(s)[0, 1, 0] < 0.01


def test_quantize_roundtrip_negative_only_page():
    """Symmetric quantization: a negative-only page keeps signs and the
    most-negative element lands on (not past) the clip boundary."""
    w = -jnp.abs(jax.random.normal(jax.random.key(9), (PAGE, 2, 8))) - 0.1
    q, s = quantize_kv_pages(w)
    dq = np.asarray(dequantize_kv_pages(q, s))
    assert np.asarray(q).min() >= -127 and np.asarray(q).max() <= 0
    assert (dq <= 0).all()
    err = np.abs(dq - np.asarray(w))
    assert err.max() <= np.asarray(s).max() / 2 + 1e-7


def test_quantize_window_batch_shapes():
    """Window form (b, W, ps, n_kv, hd) — the in-model write path's
    shape — scales per (page, head) with keepdims."""
    w = jax.random.normal(jax.random.key(10), (2, 3, PAGE, 2, 8))
    q, s = quantize_kv_pages(w)
    assert q.shape == w.shape and s.shape == (2, 3, 1, 2, 1)
    err = np.abs(np.asarray(dequantize_kv_pages(q, s)) - np.asarray(w))
    assert err.max() <= np.asarray(s).max() / 2 + 1e-7


# ------------------------------------------------------- config + sizing

def test_page_dtype_requires_paged_and_validates():
    cfg = LlamaConfig(**TINY)
    with pytest.raises(ValueError, match="paged mode"):
        CausalLM(cfg, {}, LlamaForCausalLM, page_dtype="int8")
    with pytest.raises(ValueError, match="page_dtype"):
        CausalLM(cfg, {}, LlamaForCausalLM, page_size=PAGE,
                 page_dtype="int4")


def test_int8_pool_bytes_halved_at_equal_page_count(stack):
    """THE capacity claim: per-chip KV pool bytes ≤ 0.55× fp32 at the
    SAME page count (int8 pages + fp32 scales ≈ 0.28× here), slab
    baseline unchanged (it is the un-quantized competitor), and the
    per-page sizing units dtype-aware — the tier/handoff capacity math
    admits ~2× (actually ~3.5×) pages per byte budget."""
    lm_g, lm_i = stack
    g, i = lm_g.kv_cache_bytes(), lm_i.kv_cache_bytes()
    assert i["kv_bytes"] <= 0.55 * g["kv_bytes"]
    assert i["kv_bytes_global"] <= 0.55 * g["kv_bytes_global"]
    assert i["kv_slab_bytes"] == g["kv_slab_bytes"]
    assert lm_i.kv_page_bytes() <= 0.55 * lm_g.kv_page_bytes()
    assert lm_i.kv_page_bytes_host() <= 0.55 * lm_g.kv_page_bytes_host()


def test_scale_leaf_partition_spec_follows_pool():
    """Scale leaves shard the n_kv (-2) axis exactly like their pools —
    and degrade to replicated together when heads don't divide."""
    pool = (4, 16, PAGE, 2, 8)       # (L, npages, ps, n_kv, hd)
    scale = (4, 16, 1, 2, 1)
    for tp in (1, 2):
        ps_pool = leaf_partition_spec("['cached_key']", pool, tp)
        ps_scale = leaf_partition_spec("['cached_key_scale']", scale, tp)
        assert ps_pool == ps_scale
    assert leaf_partition_spec("['cached_value_scale']", scale, 2)[-2] == "tp"
    # 2 kv heads don't divide tp=3 -> both replicated
    assert leaf_partition_spec("['cached_key_scale']", scale, 3) == \
        leaf_partition_spec("['cached_key']", pool, 3)


# ------------------------------------------------------------- the report

def test_report_names_the_page_dtype_it_measured_under(stack):
    """The serving report names the storage knob its pool bytes were measured
    under, and the int8 pool's bytes are the smaller."""
    reps = [run_trace(ServeEngine(lm, block_steps=K, rng=jax.random.key(42)),
                      [dict(prompt=_prompts(1)[0].tolist(), max_new_tokens=4)])
            for lm in stack]
    assert [r["page_dtype"] for r in reps] == ["float32", "int8"]
    assert reps[1]["kv_hbm_bytes"] <= 0.55 * reps[0]["kv_hbm_bytes"]


# ------------------------------------------------- int8 bounded divergence

def test_int8_insert_logit_delta_bounded(stack):
    """Quantized-KV prefill logits stay within a small bound of fp32 —
    the 'max logit delta' half of the bounded-divergence oracle."""
    lm_g, lm_i = stack
    p = _prompts(2, seed=11)
    ref = np.asarray(lm_g.insert(lm_g.start_session(), np.arange(2), p))
    out = np.asarray(lm_i.insert(lm_i.start_session(), np.arange(2), p))
    delta = np.abs(out - ref).max()
    assert delta < 0.25, delta


def test_int8_greedy_match_rate(stack):
    """The 'greedy-token-match ≥ 0.99' half: int8 streams vs the fp32
    gather oracle over a greedy multi-request schedule."""
    lm_g, lm_i = stack
    p = _prompts(3, seed=21)
    submits = [dict(prompt=p[i], max_new_tokens=10, arrival_block=i)
               for i in range(3)]
    ref = _streams(_serve(lm_g, submits))
    out = _streams(_serve(lm_i, submits))
    toks = [(a, b) for r in ref for a, b in zip(ref[r], out[r])]
    match = sum(a == b for a, b in toks) / len(toks)
    assert match >= 0.99, match


def test_int8_corrupt_page_caught_by_crc_seam(stack):
    """Satellite gate: a garbled int8 page is CAUGHT (crc32 detection →
    replay, or tier repair when an inclusive host copy exists) and never
    decoded — the recovered stream equals the unfaulted int8 run
    bit-for-bit, through the UNCHANGED seam (the page-IO closures frame
    scale leaves with the page, so the checksum covers them too)."""
    _, lm_i = stack
    p = _prompts(1, seed=41)
    submits = [dict(prompt=p[0], max_new_tokens=10)]
    golden = _streams(_serve(lm_i, submits))
    eng = ServeEngine(lm_i, block_steps=K, rng=jax.random.key(42))
    rid = eng.submit(p[0], 10)
    eng.step_block()
    slot = next(i for i, r in enumerate(eng.slots) if r is not None)
    victim = eng.session.paged.slot_pages(slot)[0]
    eng.inject_page_corruption([victim])
    assert eng.stats["corrupt_page_replays"] == 1
    comps = {c.request_id: c for c in eng.run()}
    assert comps[rid].tokens.tolist() == golden[0]


def test_int8_fault_plan_corruption_deterministic(stack):
    """FaultPlan-driven page corruption on the int8 engine: streams equal
    the no-fault oracle, and the same plan replayed makes identical
    decisions (the seam's determinism contract, now covering int8)."""
    _, lm_i = stack
    submits = _mixed_submits(seed=43)
    oracle = _streams(_serve(lm_i, submits))
    runs = []
    for _ in range(2):
        eng = _serve(lm_i, submits,
                     faults=FaultPlan(seed=5, corrupt_page_prob=0.4))
        assert eng.stats["corrupt_page_replays"] >= 1
        assert _streams(eng) == oracle
        runs.append((_streams(eng), dict(eng.stats)))
    assert runs[0] == runs[1]


def test_adopt_rejects_page_dtype_mismatch(stack):
    """A handoff sealed over a FOREIGN page dtype degrades to local
    re-prefill — structurally, before any byte is written (the
    tp_degree-mismatch discipline): streams still equal the oracle and
    every forged handoff verifies clean (rejection ≠ checksum)."""
    lm_g, _ = stack
    submits = _mixed_submits(seed=9)
    oracle = _streams(_serve(lm_g, submits))
    router = DisaggRouter(lm_g, 2, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K)
    dec = router.engines[1]
    orig, verdicts = dec.adopt_handoff, []

    def forge(h):
        assert h.page_dtype == "float32"   # stamped by the sealing worker
        h.page_dtype = "int8"              # ...now claim a foreign dtype
        out = orig(h)
        verdicts.append((out, h.verify()))
        return out

    dec.adopt_handoff = forge
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=300)
    assert _streams(router) == oracle
    assert router.stats["handoffs_degraded"] == len(submits)
    assert router.stats["handoffs_adopted"] == 0
    assert verdicts and all(v == ("degraded", True) for v in verdicts)
