"""Chunked prefill with stall-free mixed prefill+decode blocks (ISSUE 4
tentpole gates).

The tentpole's shippability claim is the exactness oracle: admitting a
prompt through fixed-budget prefill CHUNKS interleaved with the pool's
decode blocks changes NOTHING about the tokens — for the same submissions,
chunked streams are bit-identical to one-shot-insert admission across
fused/stepwise × greedy/sampled × paged/contiguous (the per-request rng
contract makes this hold even for sampled requests, although chunking
shifts every subsequent block). Plus the scheduling claims: decode
genuinely advances BETWEEN a long prompt's chunks (stall-free), the fused
decode half keeps its <= 2-host-ops-per-block contract (independently
counted via tests/helpers.py), and the paged page lifecycle is atomic
under mid-prefill pool pressure and cancel.

Tier-1 cost discipline: one module-scoped params set behind both lms
(block_steps=4 matches the sibling suites so fused-program shapes are
shared per-lm), tiny 2-layer config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.inference.paged_cache import (
    PagedKVCache,
    PagePoolExhausted,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests.helpers import decode_host_ops_per_block, dispatch_counts
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4
CHUNK = 5   # deliberately misaligned with both PAGE and the 8/16 buckets


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


def _run(lm, submits, fused=True, chunk=0, rng_seed=42, trace=False):
    eng = ServeEngine(lm, block_steps=K, fused=fused, trace=trace,
                      prefill_chunk_tokens=chunk, rng=jax.random.key(rng_seed))
    ids = [eng.submit(**kw) for kw in submits]
    comps = {c.request_id: c for c in eng.run()}
    return eng, {r: comps[r].tokens.tolist() for r in ids}


# ------------------------------------------------------ the exactness oracle

def test_chunked_bit_identical_to_oneshot_oracle(stack):
    """The acceptance gate: chunked admission (CHUNK=5 — misaligned with
    pages and buckets) == one-shot insert admission, token for token,
    across fused/stepwise × paged/contiguous, on a schedule mixing greedy
    and sampled requests, short prompts decoding while long prompts (12 and
    16 tokens > CHUNK) arrive and chunk in."""
    cfg, params, lm_c, lm_p = stack
    short = _prompts(2, s=8, seed=5)
    long12 = _prompts(1, s=12, seed=6)[0]
    long16 = _prompts(1, s=16, seed=7)[0]
    submits = [dict(prompt=short[0], max_new_tokens=10),
               dict(prompt=long12, max_new_tokens=6, arrival_block=1),
               dict(prompt=short[1], max_new_tokens=7,
                    sampler=Sampler(temperature=0.8), arrival_block=1),
               dict(prompt=long16, max_new_tokens=5,
                    sampler=Sampler(temperature=1.3), arrival_block=2)]
    results = {}
    for name, lm in (("contig", lm_c), ("paged", lm_p)):
        for fused in (True, False):
            for chunk in (0, CHUNK):
                eng, res = _run(lm, submits, fused=fused, chunk=chunk)
                results[(name, fused, chunk)] = res
                if chunk:
                    # the long prompts really took the chunked path
                    assert eng.stats["chunk_program_calls"] >= 2
                    assert eng.stats["prefill_chunk_tokens_done"] >= 28
    base = results[("contig", True, 0)]
    for key, res in results.items():
        assert res == base, key
    # greedy rows equal their solo generates (the PR 2 invariant holds
    # through the chunked path too)
    g0 = lm_c.generate(short[0:1], max_new_tokens=10)
    assert base[0] == g0.tokens[0].tolist()
    g1 = lm_c.generate(long12[None], max_new_tokens=6)
    assert base[1] == g1.tokens[0].tolist()


def test_decode_advances_during_chunked_prefill(stack):
    """The stall-free claim at the schedule level: while a long prompt is
    mid-chunked-prefill, the already-active slot keeps emitting K tokens
    per round — decode blocks genuinely interleave with the chunks instead
    of waiting for the insert to finish."""
    cfg, params, lm_c, lm_p = stack
    eng = ServeEngine(lm_c, block_steps=K, prefill_chunk_tokens=4,
                      rng=jax.random.key(3))
    # 4-token prompt == chunk budget -> one-shot insert; the 16-token prompt
    # is the chunked long tail
    short = eng.submit(_prompts(1, s=4, seed=9)[0], 24)
    assert eng.step_block()                   # short admitted + first block
    long_r = eng.submit(_prompts(1, s=16, seed=11)[0], 4)
    prefill_rounds = 0
    # drive rounds until the long prompt's chunked prefill completes (its
    # tiny budget may finish AND retire it within the finish round)
    while (long_r not in eng._out
           and not any(c.request_id == long_r for c in eng.completed)):
        before = len(eng._out[short])
        assert eng.step_block()
        assert len(eng._out[short]) >= before + K, \
            "active slot stalled during a prefill chunk"
        prefill_rounds += 1
        assert prefill_rounds < 10
    assert prefill_rounds >= 16 // 4          # the prefill DID span rounds
    eng.run()
    golden = lm_c.generate(_prompts(1, s=4, seed=9), max_new_tokens=24)
    done = {c.request_id: c for c in eng.completed}
    assert done[short].tokens.tolist() == golden.tokens[0].tolist()


def test_chunked_dispatch_contract(stack):
    """The fused decode half keeps <= 2 host ops per K-token block under
    chunking, counted from the engine tracer's dispatch spans (so the
    contract is also proven WITH tracing on), and chunk extends are
    accounted separately — exactly one extend dispatch per chunk."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(1, s=8, seed=13)[0]
    long16 = _prompts(1, s=16, seed=15)[0]
    eng, res = _run(lm_c, [dict(prompt=p, max_new_tokens=10),
                           dict(prompt=long16, max_new_tokens=5,
                                arrival_block=1)], chunk=4, trace=True)
    counts = dispatch_counts(eng)
    assert counts["decode"] == eng.stats["decode_blocks"] >= 2
    assert eng.stats["program_calls"] == eng.stats["host_fetches"] \
        == counts["decode"] == counts["fetch"]
    assert decode_host_ops_per_block(eng) == 2.0
    # BOTH prompts exceed the 4-token budget, so both chunk: 8/4 + 16/4
    assert eng.stats["chunk_program_calls"] == counts["extend"] == 8 // 4 + 16 // 4
    assert eng.stats["prefill_chunk_tokens_done"] == 8 + 16


# ------------------------------------------------------------- edge cases

def test_chunk_boundary_equals_bucket_boundary(stack):
    """Chunk size == prefill bucket (8): every chunk is an exact-fit bucket
    ride (no pad tail at all) and the stream still equals the one-shot
    oracle and solo generate."""
    cfg, params, lm_c, lm_p = stack
    p16 = _prompts(1, s=16, seed=17)[0]
    _, chunked = _run(lm_c, [dict(prompt=p16, max_new_tokens=6)], chunk=8)
    _, oneshot = _run(lm_c, [dict(prompt=p16, max_new_tokens=6)], chunk=0)
    assert chunked == oneshot
    g = lm_c.generate(p16[None], max_new_tokens=6)
    assert chunked[0] == g.tokens[0].tolist()
    assert (1, 8) in lm_c._chunk_extend    # chunks rode the exact-fit bucket


def test_chunk_smaller_than_kv_page(stack):
    """Paged chunks smaller than a page (3 < PAGE=4): chunks end mid-page,
    later chunks keep writing into the already-owned page, page allocation
    happens only at boundary crossings — stream equals the contiguous
    oracle."""
    cfg, params, lm_c, lm_p = stack
    p12 = _prompts(1, s=12, seed=19)[0]
    _, paged = _run(lm_p, [dict(prompt=p12, max_new_tokens=6)], chunk=3)
    g = lm_c.generate(p12[None], max_new_tokens=6)
    assert paged[0] == g.tokens[0].tolist()


def test_prompt_beyond_largest_bucket_served_chunked(stack):
    """Chunking lifts the bucket ceiling: a 20-token prompt (> largest
    bucket 16) is rejected one-shot but serves chunked, with all four
    chunked modes bit-identical."""
    cfg, params, lm_c, lm_p = stack
    p20 = _prompts(1, s=20, seed=21)[0]
    eng = ServeEngine(lm_c, block_steps=K)
    with pytest.raises(ValueError, match="largest bucket"):
        eng.submit(p20, 4)
    results = {}
    for name, lm in (("contig", lm_c), ("paged", lm_p)):
        for fused in (True, False):
            _, results[(name, fused)] = _run(
                lm, [dict(prompt=p20, max_new_tokens=4)], fused=fused, chunk=8)
    base = results[("contig", True)]
    assert len(base[0]) == 4
    for key, res in results.items():
        assert res == base, key


def test_pool_exhaustion_mid_chunk_rolls_back_atomically(stack):
    """Pool pressure MID-prefill: the long request's chunked admission
    aborts (every held page released in one step), requeues, and completes
    once the short tenant retires — streams still equal the contiguous
    oracle and the allocator drains to zero (no page leak across the
    abort/retry cycle)."""
    cfg, params, lm_c, lm_p = stack
    # 3 scratch + 9 allocatable. Short: 8 prompt + 16 new + K -> 7 pages
    # held until it retires. Long: 16 prompt + 6 new + K -> 7 pages; only 2
    # are free while the short tenant lives, so the long's chunked prefill
    # exhausts the pool MID-prompt and must abort/retry.
    lm_s = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE, page_pool_pages=12,
                    prefix_cache=False)
    short = _prompts(1, s=8, seed=23)[0]
    long16 = _prompts(1, s=16, seed=25)[0]
    eng, res = _run(lm_s, [dict(prompt=short, max_new_tokens=16),
                           dict(prompt=long16, max_new_tokens=6,
                                arrival_block=1)], chunk=4)
    assert eng.stats["prefill_aborts"] >= 1
    g_short = lm_c.generate(short[None], max_new_tokens=16)
    g_long = lm_c.generate(long16[None], max_new_tokens=6)
    assert res[0] == g_short.tokens[0].tolist()
    assert res[1] == g_long.tokens[0].tolist()
    # atomic rollback left no page behind (prefix cache off -> in_use == 0)
    assert eng.session.paged.allocator.in_use() == 0


def test_cancel_request_in_every_state(stack):
    """cancel() retires a request queued, MID-CHUNKED-PREFILL (the slot
    frees, pages roll back) or decoding (partial completion) — and the
    freed slot serves the next request with an unperturbed stream."""
    cfg, params, lm_c, lm_p = stack
    eng = ServeEngine(lm_p, block_steps=K, prefill_chunk_tokens=4,
                      rng=jax.random.key(5))
    in_use0 = eng.session.paged.allocator.in_use()
    r_dec = eng.submit(_prompts(1, s=8, seed=27)[0], 20)
    r_pre = eng.submit(_prompts(1, s=16, seed=29)[0], 6)
    r_q = eng.submit(_prompts(1, s=8, seed=31)[0], 4, arrival_block=50)
    eng.step_block()
    assert any(st.req.request_id == r_pre for st in eng._prefilling.values())
    assert eng.cancel(r_q)                      # queued
    assert eng.cancel(r_pre)                    # mid-prefill
    assert not any(st.req.request_id == r_pre
                   for st in eng._prefilling.values())
    eng.step_block()
    assert eng.cancel(r_dec)                    # decoding -> partial
    assert eng.cancel(r_dec) is False           # already gone
    partial = [c for c in eng.completed if c.request_id == r_dec]
    assert len(partial) == 1 and partial[0].cancelled
    assert 0 < len(partial[0].tokens) < 20
    # the freed slots serve a fresh request bit-identically
    p_new = _prompts(1, s=8, seed=33)[0]
    r_new = eng.submit(p_new, 6)
    comps = {c.request_id: c for c in eng.run()}
    g = lm_c.generate(p_new[None], max_new_tokens=6)
    assert comps[r_new].tokens.tolist() == g.tokens[0].tolist()
    assert eng.stats["cancelled"] == 3
    # every cancelled tenant's pages went back (prefix-cached pages of the
    # COMPLETED request may stay resident; compare against the free-pool
    # baseline after releasing nothing else)
    assert eng.session.paged.allocator.in_use() <= in_use0 + \
        eng.session.paged.prefix.cached_pages


def test_chunked_prefix_hit_skips_shared_pages(stack):
    """Chunked admission still rides the radix prefix cache: a sharer's
    chunked prefill starts AFTER the reused pages and the stream equals the
    cold contiguous oracle."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(1, s=16, seed=35)[0]
    sharer = p.copy()
    sharer[13:] = (sharer[13:] + 11) % 126 + 1
    eng = ServeEngine(lm_p, block_steps=K, prefill_chunk_tokens=5,
                      rng=jax.random.key(7))
    eng.submit(p, 4)
    eng.run()
    hits0 = eng.session.paged.stats["prefix_hit_tokens"]
    r2 = eng.submit(sharer, 6)
    comps = {c.request_id: c for c in eng.run()}
    assert eng.session.paged.stats["prefix_hit_tokens"] > hits0
    g = lm_c.generate(sharer[None], max_new_tokens=6)
    assert comps[r2].tokens.tolist() == g.tokens[0].tolist()


# ------------------------------------------- host units + trace/report

def test_paged_chunked_lifecycle_host_units():
    """begin/extend/finish/abort page math without a device: incremental
    allocation at page-boundary crossings, final extend covers the decode
    reserve, abort releases every hold atomically."""
    pkv = PagedKVCache(page_size=4, num_pages=12, max_batch=2, max_seq_len=64)
    toks = list(range(1, 15))                       # 14 tokens
    st = pkv.begin_chunked(toks, reserve_total=20)  # ceil(20/4)=5 pages total
    assert st.start == 0 and st.owned == []
    pkv.extend_chunked(st, 3)                       # mid-page: 1 page
    assert len(st.owned) == 1
    pkv.extend_chunked(st, 4)                       # boundary: still 1 page
    assert len(st.owned) == 1
    pkv.extend_chunked(st, 9)                       # 3 pages
    assert len(st.owned) == 3
    pkv.extend_chunked(st, 14, final=True)          # reserve: 5 pages
    assert len(st.owned) == 5
    table = pkv.chunk_table(0, st)
    assert list(table[:5]) == st.owned
    assert (table[5:] == pkv.scratch[0]).all()
    pkv.finish_chunked(0, st)
    assert (pkv.tables[0][:5] == st.owned).all()
    # a sharer now hits the 3 fully-covered prompt pages
    st2 = pkv.begin_chunked(toks[:12] + [99, 98], reserve_total=16)
    assert st2.start == 12 and st2.shared == st.owned[:3]
    pkv.abort_chunked(1, st2)
    assert st2.shared == [] and (pkv.tables[1] == pkv.scratch[1]).all()
    # exhaustion leaves state untouched
    st3 = pkv.begin_chunked([7] * 9, reserve_total=60)   # needs 15 pages
    with pytest.raises(PagePoolExhausted):
        pkv.extend_chunked(st3, 9, final=True)
    assert st3.owned == []
    pkv.abort_chunked(1, st3)


def test_synthetic_trace_heavy_tail_and_report(stack):
    """ISSUE 4 satellite: long_prompt_frac/long_prompt_len make the
    interference workload constructible, and run_trace reports per-request
    TTFT + max inter-token gap plus the chunk accounting."""
    cfg, params, lm_c, lm_p = stack
    trace = synthetic_trace(6, 128, prompt_lens=(8,), max_new_tokens=5,
                            mean_interarrival_blocks=0.5,
                            long_prompt_frac=1 / 3, long_prompt_len=16, seed=3)
    lens = [len(t["prompt"]) for t in trace]
    assert lens == [8, 8, 16, 8, 8, 16]       # every 3rd request heavy
    eng = ServeEngine(lm_c, block_steps=K, prefill_chunk_tokens=8)
    rep = run_trace(eng, trace)
    assert rep["requests_completed"] == 6
    assert rep["host_ops_per_block"] == 2.0   # decode half untouched
    assert rep["prefill_chunk_tokens"] == 8
    assert rep["chunk_program_calls"] >= 4    # two 16-token prompts chunked
    assert rep["prefill_chunk_tokens_done"] == 32
    assert len(rep["per_request"]) == 6
    long_reqs = [r for r in rep["per_request"] if r["prompt_len"] == 16]
    assert all(r["ttft_blocks"] >= 1 for r in long_reqs)
    assert rep["itl_p99_ms"] is not None and rep["max_itl_gap_ms"] >= 0
    with pytest.raises(ValueError, match="long_prompt_len"):
        synthetic_trace(4, 128, long_prompt_frac=0.5)


def test_engine_chunk_validation(stack):
    cfg, params, lm_c, lm_p = stack
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ServeEngine(lm_c, block_steps=K, prefill_chunk_tokens=-1)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        ServeEngine(lm_c, block_steps=K, prefill_chunk_tokens=32)
    # chunked or not, a prompt that cannot fit the cache room is rejected
    eng = ServeEngine(lm_c, block_steps=K, prefill_chunk_tokens=8)
    with pytest.raises(ValueError, match="cache room"):
        eng.submit(_prompts(1, s=40, seed=1)[0], 40)
