"""Paged decode-attention kernel + int8 KV pages (ISSUE 17 gates).

Two oracles, two disciplines:

* **fp32 pages, kernel path**: token streams BIT-IDENTICAL to the gather
  reference across fused/stepwise × greedy/sampled × prefix-hit/cold ×
  chunked prefill × disagg adopt-handoff, and TP=2 ≡ TP=1. (Logits agree
  to online-softmax reassociation distance — the argmax/sampled-token
  STREAM is the pinned surface, the same bar every serving suite uses.)
* **int8 pages**: bounded divergence — per-page quantize/dequantize
  round-trip units (absmax edge cases), insert-logit max-delta bound,
  greedy-token-match vs the fp32 oracle, pool bytes ≤ 0.55× fp32 at
  equal page count, and the crc32/repair seam catching a garbled int8
  page before it is ever decoded.

Kernel units drive :func:`paged_decode_attention` (interpret mode on CPU
— the REAL kernel semantics) directly against
:func:`reference_paged_attention`, which mirrors ``_decode_attention``'s
gather branch exactly.

Tier-1 cost discipline: one module-scoped param set behind every lm
(test_paged_cache's tiny dims, block_steps=K shared), TP worlds built
once and reused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import (
    CausalLM,
    DisaggRouter,
    FaultPlan,
    Sampler,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.replay import run_trace
from neuronx_distributed_tpu.inference.paged_kernel import (
    dequantize_kv_pages,
    paged_decode_attention,
    paged_kernel_supported,
    quantize_kv_pages,
    reference_paged_attention,
)
from neuronx_distributed_tpu.inference.partition import leaf_partition_spec
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4


@pytest.fixture(scope="module")
def stack():
    """(gather lm, kernel lm, int8+kernel lm) over ONE weight set — the
    gather lm is the reference oracle for both kernel lms."""
    cfg = LlamaConfig(**TINY)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = meta.unbox(
        LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), ids))["params"]

    def mk(**kw):
        return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                        max_batch=3, page_size=PAGE, **kw).compile()

    return mk(), mk(paged_attn_kernel=True), mk(page_dtype="int8",
                                                paged_attn_kernel=True)


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


# ------------------------------------------------------------ kernel units

def _rand_pool(key, num_pages, ps, n_kv, hd):
    kk, kv = jax.random.split(key)
    return (jax.random.normal(kk, (num_pages, ps, n_kv, hd), jnp.float32),
            jax.random.normal(kv, (num_pages, ps, n_kv, hd), jnp.float32))


def test_paged_kernel_supported_gate():
    assert paged_kernel_supported(1, 4, 8, 2)
    assert paged_kernel_supported(1, 4, 4, 4)       # MHA group=1
    assert not paged_kernel_supported(2, 4, 8, 2)   # multi-token step
    assert not paged_kernel_supported(1, 4, 6, 4)   # non-integral group


def test_kernel_matches_reference_ragged_gqa():
    """The core exactness unit: ragged lengths (incl. a length-0 row
    attending only its own fresh token), GQA grouping, PERMUTED block
    tables — kernel output tracks the gather+dense reference to fp32
    reassociation distance, eagerly and under jit."""
    b, ps, n_kv, group, hd, ppseq = 3, 4, 2, 3, 16, 8
    num_pages = b * ppseq + 1
    k_pages, v_pages = _rand_pool(jax.random.key(0), num_pages, ps, n_kv, hd)
    q = jax.random.normal(jax.random.key(1), (b, 1, n_kv * group, hd),
                          jnp.float32)
    # each row's pages shuffled through the pool — the paged indirection
    table = jax.random.permutation(
        jax.random.key(2), num_pages - 1)[:b * ppseq].reshape(b, ppseq)
    table = table.astype(jnp.int32)
    cache_len = jnp.asarray([0, 7, 29], jnp.int32)
    ref = reference_paged_attention(q, k_pages, v_pages, table, cache_len)
    out = paged_decode_attention(q, k_pages, v_pages, table, cache_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    jout = jax.jit(paged_decode_attention)(q, k_pages, v_pages, table,
                                           cache_len)
    np.testing.assert_allclose(np.asarray(jout), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_kernel_ignores_stale_page_bytes():
    """Positions past ``cache_len`` — stale bytes in reused pages, whole
    unvisited pages — contribute EXACTLY zero probability mass: poisoning
    them with huge values must not move the output (the reference runs on
    the clean pool; the kernel on the poisoned one)."""
    b, ps, n_kv, group, hd, ppseq = 2, 4, 2, 2, 8, 4
    num_pages = b * ppseq + 1
    k_pages, v_pages = _rand_pool(jax.random.key(3), num_pages, ps, n_kv, hd)
    q = jax.random.normal(jax.random.key(4), (b, 1, n_kv * group, hd),
                          jnp.float32)
    table = jnp.arange(b * ppseq, dtype=jnp.int32).reshape(b, ppseq)
    cache_len = jnp.asarray([5, 9], jnp.int32)
    ref = reference_paged_attention(q, k_pages, v_pages, table, cache_len)
    # poison every position strictly above each row's qpos (same page and
    # beyond) with large-magnitude garbage
    pos = (jnp.arange(num_pages * ps) % ps
           + (jnp.arange(num_pages * ps) // ps % ppseq) * ps)
    flat_pos = jnp.repeat(jnp.arange(ppseq * ps)[None], b, 0)
    kf = k_pages.reshape(num_pages * ps, n_kv, hd)
    vf = v_pages.reshape(num_pages * ps, n_kv, hd)
    for row in range(b):
        row_flat = table[row, flat_pos[row] // ps] * ps + flat_pos[row] % ps
        bad = row_flat[flat_pos[row] > cache_len[row]]
        kf = kf.at[bad].set(1e4)
        vf = vf.at[bad].set(-1e4)
    del pos
    out = paged_decode_attention(
        q, kf.reshape(num_pages, ps, n_kv, hd),
        vf.reshape(num_pages, ps, n_kv, hd), table, cache_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_kernel_int8_dequant_matches_reference():
    """int8 pools: the in-tile dequant multiply reproduces the gather
    reference's dequantize-then-attend to reassociation distance — both
    consume the SAME quantized values, so this isolates the kernel's
    dequant placement, not quantization error."""
    b, ps, n_kv, group, hd, ppseq = 2, 4, 2, 4, 8, 4
    num_pages = b * ppseq + 1
    kf, vf = _rand_pool(jax.random.key(5), num_pages, ps, n_kv, hd)
    kq, ks = quantize_kv_pages(kf)
    vq, vs = quantize_kv_pages(vf)
    q = jax.random.normal(jax.random.key(6), (b, 1, n_kv * group, hd),
                          jnp.float32)
    table = jax.random.permutation(
        jax.random.key(7), b * ppseq).reshape(b, ppseq).astype(jnp.int32)
    cache_len = jnp.asarray([3, 14], jnp.int32)
    ref = reference_paged_attention(q, kq, vq, table, cache_len,
                                    k_scale=ks, v_scale=vs)
    out = paged_decode_attention(q, kq, vq, table, cache_len,
                                 k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_kernel_input_validation():
    k = jnp.zeros((4, 4, 2, 8))
    bt = jnp.zeros((1, 4), jnp.int32)
    cl = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="single-token"):
        paged_decode_attention(jnp.zeros((1, 2, 4, 8)), k, k, bt, cl)
    with pytest.raises(ValueError, match="multiple"):
        paged_decode_attention(jnp.zeros((1, 1, 3, 8)), k, k, bt, cl)
    with pytest.raises(ValueError, match="BOTH"):
        paged_decode_attention(jnp.zeros((1, 1, 4, 8)), k, k, bt, cl,
                               k_scale=jnp.ones((4, 1, 2, 1)))


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
    with pytest.raises(ValueError, match="paged mode"):
        CausalLM(cfg, {}, LlamaForCausalLM, paged_attn_kernel=True)
    with pytest.raises(ValueError, match="page_dtype"):
        CausalLM(cfg, {}, LlamaForCausalLM, page_size=PAGE,
                 page_dtype="int4")


def test_int8_pool_bytes_halved_at_equal_page_count(stack):
    """THE capacity claim: per-chip KV pool bytes ≤ 0.55× fp32 at the
    SAME page count (int8 pages + fp32 scales ≈ 0.28× here), slab
    baseline unchanged (it is the un-quantized competitor), and the
    per-page sizing units dtype-aware — the tier/handoff capacity math
    admits ~2× (actually ~3.5×) pages per byte budget."""
    lm_g, lm_k, lm_i = stack
    g, i = lm_g.kv_cache_bytes(), lm_i.kv_cache_bytes()
    assert i["kv_bytes"] <= 0.55 * g["kv_bytes"]
    assert i["kv_bytes_global"] <= 0.55 * g["kv_bytes_global"]
    assert i["kv_slab_bytes"] == g["kv_slab_bytes"]
    assert lm_i.kv_page_bytes() <= 0.55 * lm_g.kv_page_bytes()
    assert lm_i.kv_page_bytes_host() <= 0.55 * lm_g.kv_page_bytes_host()
    # kernel-only lm: storage untouched, sizing identical to gather
    assert lm_k.kv_cache_bytes() == g


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


# ----------------------------------------- the serving exactness matrix

def test_kernel_streams_bit_identical_fused_and_stepwise(stack):
    """THE fp32 acceptance gate: kernel-path token streams equal the
    gather reference bit-for-bit — greedy and sampled rows decoding in
    neighbouring slots, both decode modes."""
    lm_g, lm_k, _ = stack
    submits = _mixed_submits()
    for fused in (True, False):
        ref = _streams(_serve(lm_g, submits, fused=fused))
        out = _streams(_serve(lm_k, submits, fused=fused))
        assert out == ref, fused


def test_kernel_prefix_hit_and_cold_exact(stack):
    """Prefix-shared and prefix-cold admissions through the kernel path:
    streams equal the gather engine's on the same schedule, and the
    kernel engine actually exercised a radix hit (the shared pages are
    read through the block table like any others)."""
    lm_g, lm_k, _ = stack
    base = _prompts(1, seed=31)[0]
    fam = np.stack([base, np.concatenate([base[:PAGE], [99, 98, 97, 96]])])
    submits = [dict(prompt=fam[0], max_new_tokens=6),
               dict(prompt=fam[0], max_new_tokens=8, arrival_block=1),
               dict(prompt=fam[1], max_new_tokens=6, arrival_block=2)]
    ref_eng = _serve(lm_g, submits)
    out_eng = _serve(lm_k, submits)
    assert _streams(out_eng) == _streams(ref_eng)
    assert out_eng.session.paged.stats["prefix_hits"] > 0


def test_kernel_chunked_prefill_exact(stack):
    """Chunked prefill (multi-token extends keep the gather path; the
    kernel takes over at the single-token decode steps): streams equal
    the gather engine chunked AND the one-shot oracle."""
    lm_g, lm_k, _ = stack
    p = np.concatenate([_prompts(1, s=14, seed=33)[0], [0, 0]])  # pad tail
    submits = [dict(prompt=p, max_new_tokens=8),
               dict(prompt=_prompts(1, seed=34)[0], max_new_tokens=6,
                    arrival_block=1)]
    oneshot = _streams(_serve(lm_g, submits))
    ref = _streams(_serve(lm_g, submits, prefill_chunk_tokens=5))
    out = _streams(_serve(lm_k, submits, prefill_chunk_tokens=5))
    assert out == ref == oneshot


def test_kernel_disagg_adopt_exact(stack):
    """Adopt-handoff leg: a prefill→decode migration whose decode worker
    runs the kernel path serves bit-identical to the single gather
    engine — adopted pages are ordinary pool pages to the kernel."""
    lm_g, lm_k, _ = stack
    submits = _mixed_submits(seed=7)
    oracle = _streams(_serve(lm_g, submits))
    router = DisaggRouter(lm_k, 2, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K)
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=300)
    assert _streams(router) == oracle
    assert router.stats["handoffs_adopted"] == len(submits)
    assert router.stats["handoffs_degraded"] == 0


def test_kernel_host_ops_contract_and_report(stack):
    """The ≤2-host-ops-per-fused-block dispatch contract holds with the
    kernel enabled, and the serving report names the storage/kernel knobs
    it measured under."""
    from tests.helpers import decode_host_ops_per_block

    _, lm_k, lm_i = stack
    eng = ServeEngine(lm_k, block_steps=K, rng=jax.random.key(42),
                      trace=True)
    for kw in _mixed_submits():
        eng.submit(**kw)
    rep = run_trace(eng, [])
    assert decode_host_ops_per_block(eng) == 2.0
    assert rep["paged_attn_kernel"] is True
    assert rep["page_dtype"] == "float32"
    rep_i = run_trace(
        ServeEngine(lm_i, block_steps=K, rng=jax.random.key(42)),
        [dict(prompt=_prompts(1)[0].tolist(), max_new_tokens=4)])
    assert rep_i["page_dtype"] == "int8"
    assert rep_i["kv_hbm_bytes"] <= 0.55 * rep["kv_hbm_bytes"]


# ------------------------------------------------- int8 bounded divergence

def test_int8_insert_logit_delta_bounded(stack):
    """Quantized-KV prefill logits stay within a small bound of fp32 —
    the 'max logit delta' half of the bounded-divergence oracle."""
    lm_g, _, lm_i = stack
    p = _prompts(2, seed=11)
    ref = np.asarray(lm_g.insert(lm_g.start_session(), np.arange(2), p))
    out = np.asarray(lm_i.insert(lm_i.start_session(), np.arange(2), p))
    delta = np.abs(out - ref).max()
    assert delta < 0.25, delta


def test_int8_greedy_match_rate(stack):
    """The 'greedy-token-match ≥ 0.99' half: int8 streams vs the fp32
    gather oracle over a greedy multi-request schedule."""
    lm_g, _, lm_i = stack
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
    _, _, lm_i = stack
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
    _, _, lm_i = stack
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
    lm_g, lm_k, _ = stack
    submits = _mixed_submits(seed=9)
    oracle = _streams(_serve(lm_g, submits))
    router = DisaggRouter(lm_k, 2, prefill_replicas=1,
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


# --------------------------------------------------------------- TP worlds

def test_tp2_kernel_streams_bit_identical_to_tp1():
    """TP=2 acceptance leg: the kernel's head-axis grid tiles never cross
    the TP shard, so sharding the pools changes the layout, not one
    token — TP=2 kernel streams equal TP=1 kernel streams equal the
    TP=1 gather oracle."""
    from neuronx_distributed_tpu.parallel import mesh as psm
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model,
        neuronx_distributed_config,
    )

    cfg = LlamaConfig(**TINY)
    submits = _mixed_submits(seed=13)
    streams = {}
    try:
        for tp, kernel in ((1, False), (1, True), (2, True)):
            psm.destroy_model_parallel()
            psm.initialize_model_parallel(tensor_model_parallel_size=tp)
            nxd = neuronx_distributed_config(tensor_parallel_size=tp)
            model = initialize_parallel_model(
                nxd, lambda: LlamaForCausalLM(cfg),
                jnp.zeros((1, 8), jnp.int32))
            lm = CausalLM(cfg, model.params, LlamaForCausalLM,
                          buckets=(8, 16), max_batch=3, page_size=PAGE,
                          paged_attn_kernel=kernel).compile()
            streams[(tp, kernel)] = _streams(_serve(lm, submits))
    finally:
        psm.destroy_model_parallel()
    assert streams[(2, True)] == streams[(1, True)] == streams[(1, False)]
