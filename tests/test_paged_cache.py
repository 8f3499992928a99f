"""Paged KV cache + shared-prefix reuse (ISSUE 3 tentpole gates).

The paged subsystem's shippability claim is the exactness oracle: for the
SAME schedule, the paged engine (block-table page pool, prefix sharing,
page-freeing retire) emits token streams BIT-identical to the contiguous-
slot engine of PR 2 — fused and stepwise, greedy and sampled, prefix-shared
and prefix-cold mixes, staggered insert/retire. Plus the allocator-level
contracts: inserts touch only owned pages, freed pages are reusable with no
stale-KV bleed, pool pressure defers admission instead of corrupting state,
and the host allocator/radix index behave (unit tests, no device).

Tier-1 cost discipline: one module-scoped params set behind BOTH lms
(block_steps=4 matches test_serving_engine's K so fused-program shapes are
shared per-lm), tiny 2-layer config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.inference.paged_cache import (
    PageAllocator,
    PagedKVCache,
    PagePoolExhausted,
    RadixPrefixIndex,
)
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
    """(config, params, contiguous lm, paged lm) over ONE weight set."""
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm_c = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3).compile()
    lm_p = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE).compile()
    return cfg, params, lm_c, lm_p


def _prompts(n, s=8, seed=2):
    return np.array(jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _run(lm, submits, fused=True, rng_seed=42, block_steps=K):
    eng = ServeEngine(lm, block_steps=block_steps, fused=fused,
                      rng=jax.random.key(rng_seed))
    ids = [eng.submit(**kw) for kw in submits]
    comps = {c.request_id: c for c in eng.run()}
    return eng, {r: comps[r].tokens.tolist() for r in ids}


# --------------------------------------------------------------- host units

def test_page_allocator_refcounts_and_free_list():
    a = PageAllocator(8, reserved=2)
    assert a.available() == 6
    pages = a.alloc(3)
    assert pages == [2, 3, 4] and a.in_use() == 3
    a.retain([2])
    assert a.release([2]) == []          # still held once
    assert a.release([2, 3, 4]) == [2, 3, 4]
    assert a.available() == 6
    assert a.alloc(7) is None            # over-ask leaves the free list intact
    assert a.available() == 6
    with pytest.raises(ValueError):
        a.release([3])                   # double free


def test_radix_prefix_index_lookup_register_evict():
    a = PageAllocator(10, reserved=0)
    idx = RadixPrefixIndex(4, a)
    toks = list(range(1, 13))            # 3 full pages
    pages = a.alloc(3)
    idx.register(toks, pages)            # cache holds rc=2
    assert idx.lookup(toks) == pages
    assert idx.lookup(toks[:7]) == pages[:1]          # page-aligned only
    assert idx.lookup([9] + toks[1:]) == []           # first page diverges
    # a diverging SECOND page shares only the first (register takes the
    # full position-aligned page list; the existing first-page node wins)
    other = a.alloc(1)
    idx.register(toks[:4] + [99, 98, 97, 96], [pages[0], other[0]])
    assert idx.lookup(toks[:4] + [99, 98, 97, 96]) == [pages[0], other[0]]
    # release the allocation holds -> pages become cache-only, evictable LRU
    a.release(pages)
    a.release(other)
    assert a.available() == 10 - 4
    freed = idx.evict(2)
    assert freed == 2 and a.available() == 10 - 2
    # surviving prefix still serves lookups
    assert idx.lookup(toks)[:1] == pages[:1]


def test_paged_kv_cache_plan_commit_release_cycle():
    pkv = PagedKVCache(page_size=4, num_pages=12, max_batch=2, max_seq_len=64)
    toks = list(range(1, 11))            # 10 tokens: 2 full pages + tail
    plan = pkv.plan(toks, reserve_total=14)          # ceil(14/4)=4 pages
    assert plan.start == 0 and len(plan.owned) == 4
    pkv.commit(0, plan, toks)
    assert (pkv.tables[0][:4] == plan.owned).all()
    assert (pkv.tables[0][4:] == pkv.scratch[0]).all()
    # a sharer reuses the 2 full prompt pages, recomputes from token 8
    plan2 = pkv.plan(toks[:8] + [101, 102], reserve_total=12)
    assert plan2.start == 8 and plan2.shared == plan.owned[:2]
    pkv.rollback(plan2)
    # release returns decode pages; prompt pages stay cached for reuse
    pkv.release(0)
    assert (pkv.tables[0] == pkv.scratch[0]).all()
    assert pkv.plan(toks, reserve_total=10).shared == plan.owned[:2]


# ------------------------------------------------- the exactness oracle

def test_paged_engine_bit_identical_to_contiguous_oracle(stack):
    """The acceptance gate: paged (fused AND stepwise) == contiguous (fused
    AND stepwise), token for token, on a schedule mixing greedy and sampled
    requests, staggered arrivals, slot churn, and a prefix-shared pair next
    to prefix-cold requests."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(4, seed=5)
    p[1, :PAGE] = p[0, :PAGE]            # page-aligned shared prefix
    submits = [dict(prompt=p[0], max_new_tokens=9),
               dict(prompt=p[1], max_new_tokens=6, arrival_block=1),
               dict(prompt=p[2], max_new_tokens=7,
                    sampler=Sampler(temperature=0.8), arrival_block=2),
               dict(prompt=p[3], max_new_tokens=5, arrival_block=3)]
    results = {}
    for name, lm in (("contig", lm_c), ("paged", lm_p)):
        for fused in (True, False):
            eng, results[(name, fused)] = _run(lm, submits, fused=fused)
            if name == "paged":
                # 4 requests through 3 slots: churn + page recycling happened
                assert eng.stats["inserted_requests"] == 4 > lm.max_batch
    base = results[("contig", True)]
    for key, res in results.items():
        assert res == base, key
    # the greedy row equals its solo generate (the PR 2 invariant holds
    # through the paged path too)
    g0 = lm_c.generate(p[0:1], max_new_tokens=9)
    assert base[0] == g0.tokens[0].tolist()
    # the prefix HIT actually happened in paged mode (not vacuous sharing)
    eng_p, _ = _run(lm_p, submits, fused=True)
    assert eng_p.session.paged.stats["prefix_hit_tokens"] >= PAGE


def _kv_pages(cache, pages):
    """The K and V pool leaves of every layer at physical ``pages``."""
    named = ((jax.tree_util.keystr(path), leaf)
             for path, leaf in jax.tree_util.tree_leaves_with_path(cache))
    return {name: np.asarray(leaf)[:, pages] for name, leaf in named
            if name.endswith(("['cached_key']", "['cached_value']"))}


def _greedy_stream(lm, sess, slot, logits, steps):
    out = []
    for _ in range(steps):
        out.append(int(np.argmax(np.asarray(logits))))
        tokens = np.zeros((lm.max_batch,), np.int32)
        tokens[slot] = out[-1]
        logits = lm.step(sess, tokens)[slot]
    return out


def test_paged_prefix_hit_skips_shared_prefill(stack):
    """A prefix-hit insert prefills ONLY the suffix: the hit request rides a
    smaller suffix bucket and reuses the shared prefix bit for bit.

    "Bit-exact prefix reuse" is a statement about the CACHE: the hit's block
    table points at the very pages the first insert wrote, and the hit
    leaves them untouched. It is not a statement about logits across chunk
    widths: the hit attends with 8 query rows and the cold insert with 16,
    and a matmul sums in an order that may depend on its row count (the
    CPU's float32 GEMM does, once the KV heads are grouped), so the two
    first-token logits agree to float32 summation order, and the greedy
    streams they lead to are equal."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(1, s=12, seed=7)[0]
    sess = lm_p.start_session()
    lm_p.insert(sess, [0], p[None], reserve_tokens=6)
    shared = [int(x) for x in sess.paged.tables[0][:2]]
    written = _kv_pages(sess.cache, shared)
    lm_p.retire(sess, [0])
    sharer = p.copy()
    sharer[9:] = (sharer[9:] + 11) % 126 + 1         # diverge in the tail
    hit_logits = lm_p.insert(sess, [1], sharer[None], reserve_tokens=6)
    st = sess.paged.stats
    assert st["prefix_hit_tokens"] == 8              # 2 of 3 pages reused
    # suffix of 4 tokens -> the (1, 8) suffix-bucket insert program, not the
    # full 16-bucket one
    assert (1, 8) in lm_p._paged_insert
    # the shared pages are the first insert's, read through the hit's table
    assert [int(x) for x in sess.paged.tables[1][:2]] == shared
    reused = _kv_pages(sess.cache, shared)
    assert written.keys() == reused.keys() and len(written) >= 2
    for name, pages in written.items():
        assert np.abs(pages).max() > 0, name
        np.testing.assert_array_equal(reused[name], pages, err_msg=name)
    # oracle: cold contiguous insert of the same sharer
    sess_c = lm_c.start_session()
    cold_logits = lm_c.insert(sess_c, [1], sharer[None])
    np.testing.assert_allclose(np.asarray(hit_logits), np.asarray(cold_logits),
                               rtol=0, atol=2e-6)
    assert (_greedy_stream(lm_p, sess, 1, hit_logits[0], 6)
            == _greedy_stream(lm_c, sess_c, 1, cold_logits[0], 6))


def test_paged_mixed_cold_and_hit_group_single_insert(stack):
    """A cold request and a prefix-hit request admitted in ONE group ride a
    single suffix-bucket insert (different per-row starts inside one
    program) and both streams stay bit-identical to the contiguous
    oracle's."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(3, seed=15)
    p[2, :PAGE] = p[0, :PAGE]
    res = {}
    for name, lm in (("contig", lm_c), ("paged", lm_p)):
        eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(7))
        eng.submit(p[0], 5)          # seeds the prefix cache, retires
        eng.run()
        r1 = eng.submit(p[1], 6)     # cold: suffix == full prompt
        r2 = eng.submit(p[2], 6)     # hit: suffix == prompt minus one page
        comps = {c.request_id: c for c in eng.run()}
        res[name] = (comps[r1].tokens.tolist(), comps[r2].tokens.tolist())
        if name == "paged":
            assert eng.stats["inserts"] == 2           # seed + the pair
            assert eng.session.paged.stats["prefix_hit_tokens"] >= PAGE
    assert res["contig"] == res["paged"]


def test_paged_retire_reuse_no_stale_kv_bleed(stack):
    """Scatter-isolation analogue: pages freed by a retired request are
    handed to a new request, and the new request's stream is bit-identical
    to its solo oracle — no stale K/V from the previous tenant leaks through
    the recycled pages (and residual writes from the retired slot land in
    scratch, never in the recycled pages)."""
    cfg, params, lm_c, lm_p = stack
    # pool: 3 scratch + 7 allocatable -> every request (8 prompt + 6 new +
    # K overrun -> ceil(18/4)=5 pages) forces reuse of freed pages
    lm_s = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE, page_pool_pages=10)
    p = _prompts(3, seed=9)
    eng = ServeEngine(lm_s, block_steps=K, rng=jax.random.key(42))
    ids = [eng.submit(p[i], 6) for i in range(3)]
    comps = {c.request_id: c for c in eng.run()}
    assert eng.stats["deferred_admissions"] >= 1     # the pool DID saturate
    for i in range(3):
        g = lm_c.generate(p[i: i + 1], max_new_tokens=6)
        assert comps[ids[i]].tokens.tolist() == g.tokens[0].tolist(), i


def test_paged_admission_defers_at_full_pool_then_completes(stack):
    """Admission at full pool occupancy (the PR 2 suite's skipped edge): all
    requests eventually complete, in submit order per slot availability, and
    the engine never wedges when the queue outsizes the pool."""
    cfg, params, lm_c, lm_p = stack
    lm_s = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE, page_pool_pages=9,
                    prefix_cache=False)              # no cache to evict: pure deferral
    p = _prompts(4, seed=11)
    eng = ServeEngine(lm_s, block_steps=K, rng=jax.random.key(1))
    for i in range(4):
        eng.submit(p[i], 5)
    comps = eng.run(max_blocks=200)
    assert len(comps) == 4
    assert eng.stats["deferred_admissions"] >= 1
    # an impossible request is rejected at submit, not deadlocked at admit
    with pytest.raises(ValueError, match="pages"):
        eng.submit(p[0], 40)


def test_paged_insert_touches_only_owned_pages(stack):
    """The paged right-sized-insert claim, checked on the pool itself:
    inserting into slot 1 leaves every page OUTSIDE the new request's table
    bit-identical (a neighbour mid-generation keeps its pages untouched)."""
    cfg, params, lm_c, lm_p = stack
    sess = lm_p.start_session()
    p = _prompts(3, seed=13)
    lm_p.insert(sess, [0], p[0:1], reserve_tokens=8)
    lm_p.step(sess, np.zeros((3,), np.int32))
    before = jax.tree.map(np.asarray, sess.cache)
    lm_p.insert(sess, [1], p[1:2], reserve_tokens=8)
    after = jax.tree.map(np.asarray, sess.cache)
    touched = set(int(x) for x in sess.paged.tables[1])

    def check(path, a, b):
        pstr = jax.tree_util.keystr(path)
        if pstr.endswith("['cached_key']") or pstr.endswith("['cached_value']"):
            keep = [i for i in range(a.shape[1]) if i not in touched]
            np.testing.assert_array_equal(a[:, keep], b[:, keep],
                                          err_msg=pstr)

    jax.tree_util.tree_map_with_path(check, before, after)


def test_paged_hbm_bytes_scale_with_pool_not_slab(stack):
    """The memory claim: a half-size pool reports ~half the slab bytes, and
    the default pool sits at slab parity + scratch."""
    cfg, params, lm_c, lm_p = stack
    kv_c = lm_c.kv_cache_bytes()
    assert kv_c["kv_bytes"] == kv_c["kv_slab_bytes"]
    half_pool = 3 * (64 // PAGE) // 2 + 3
    lm_h = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE, page_pool_pages=half_pool)
    kv_h = lm_h.kv_cache_bytes()
    assert kv_h["kv_slab_bytes"] == kv_c["kv_slab_bytes"]
    ratio = kv_h["kv_bytes"] / kv_h["kv_slab_bytes"]
    assert 0.4 < ratio < 0.65


def test_paged_run_trace_reports_paged_surface(stack):
    """run_trace on a paged engine carries the paged report keys (the
    runner.py serve --paged surface): hit accounting, pool sizing, and the
    unchanged <=2-host-ops-per-block dispatch contract."""
    cfg, params, lm_c, lm_p = stack
    # arrivals spread out so admissions are sequential: requests planned in
    # one group share nothing (plans snapshot the index at group start)
    trace = synthetic_trace(4, 128, prompt_lens=(4,), max_new_tokens=5,
                            mean_interarrival_blocks=3.0,
                            shared_prefix_len=8, seed=3)
    eng = ServeEngine(lm_p, block_steps=K)
    rep = run_trace(eng, trace)
    assert rep["requests_completed"] == 4
    assert rep["host_ops_per_block"] == 2.0
    assert rep["paged"] is True and rep["page_size"] == PAGE
    # later requests hit the 8-token shared prefix
    assert rep["prefix_queries"] == 4
    assert rep["prefix_hit_tokens"] >= 2 * 8
    assert rep["kv_hbm_bytes"] > 0 and rep["kv_hbm_vs_slab"] > 0


def test_paged_chunked_dispatch_contract(stack):
    """Chunked admission on the PAGED engine keeps the decode half's
    <= 2-host-ops-per-block contract — counted from the engine TRACER's
    dispatch spans (tests/helpers.py; the run therefore also proves the
    contract holds with tracing ON), while chunk extends ride their own
    accounting: exactly one 'extend' dispatch per chunk."""
    from tests.helpers import decode_host_ops_per_block, dispatch_counts

    cfg, params, lm_c, lm_p = stack
    p = _prompts(2, seed=17)
    long16 = _prompts(1, s=16, seed=19)[0]
    eng = ServeEngine(lm_p, block_steps=K, prefill_chunk_tokens=8,
                      rng=jax.random.key(11), trace=True)
    eng.submit(p[0], 8)
    eng.submit(long16, 5, arrival_block=1)
    comps = eng.run()
    assert len(comps) == 2
    counts = dispatch_counts(eng)
    assert counts["decode"] == eng.stats["decode_blocks"] >= 2
    assert eng.stats["program_calls"] == eng.stats["host_fetches"] \
        == counts["decode"] == counts["fetch"]
    assert decode_host_ops_per_block(eng) == 2.0
    assert eng.stats["chunk_program_calls"] == counts["extend"] == 16 // 8
    # the chunked request's stream still equals its solo oracle
    g = lm_c.generate(long16[None], max_new_tokens=5)
    by_id = {c.request_id: c for c in comps}
    assert by_id[1].tokens.tolist() == g.tokens[0].tolist()


def test_paged_guards(stack):
    cfg, params, lm_c, lm_p = stack
    with pytest.raises(ValueError, match="divide"):
        CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=2,
                 page_size=7)
    with pytest.raises(ValueError, match="contiguous"):
        lm_p.generate(_prompts(1), max_new_tokens=2)
