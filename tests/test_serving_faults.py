"""Overload- and fault-tolerant serving (ISSUE 5 tentpole gates).

Two acceptance surfaces:

* the RECOVERY ORACLE — snapshot → kill → restore mid-trace produces token
  streams bit-identical to the uninterrupted run (fused/stepwise ×
  greedy/sampled × paged/contiguous): token t of request r always draws
  from ``fold_in(fold_in(base, r), t)``, so a restored engine that replays
  prompt+generated and resumes at index len(generated) MUST reproduce the
  stream exactly — asserted, not hoped;
* the CHAOS MATRIX — under seeded fault storms (pool exhaustion, transient
  dispatch failures, corrupted pages) the engine never deadlocks, streams
  still equal the no-fault oracle, the page allocator drains to 0 after
  retire-all, and the same plan replayed twice makes identical decisions.

Plus the deadline/shedding scheduler claims: EDF admission, queued /
mid-chunked-prefill / mid-stream expiry (page rollback reused), bounded
queue with structured Rejected(retry_after) and shed-then-resubmit.

Tier-1 cost discipline: one module-scoped params set behind both lms
(block_steps=4, tiny 2-layer config — the sibling suites' shapes).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    DispatchFailed,
    FaultPlan,
    Rejected,
    Sampler,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4

CHAOS_PLAN = dict(seed=1, pool_exhaust_prob=0.3, pool_storm_len=2,
                  dispatch_fail_prob=0.25, dispatch_max_failures=2,
                  corrupt_page_prob=0.3)


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
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _mixed_submits():
    """Greedy + sampled + long (chunk-eligible) — the matrix workload."""
    p = _prompts(2, seed=5)
    p16 = _prompts(1, s=16, seed=7)[0]
    return [dict(prompt=p[0], max_new_tokens=12),
            dict(prompt=p16, max_new_tokens=8, arrival_block=1,
                 sampler=Sampler(temperature=1.3)),
            dict(prompt=p[1], max_new_tokens=10, arrival_block=1,
                 sampler=Sampler(temperature=0.8))]


def _streams(engine):
    return {c.request_id: c.tokens.tolist() for c in engine.completed}


def _oracle(lm, submits, **eng_kw):
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42), **eng_kw)
    for kw in submits:
        eng.submit(**kw)
    eng.run()
    return _streams(eng)


# ------------------------------------------------ deadlines & EDF admission

def test_deadline_expires_decoding_request_with_partial_stream(stack):
    """A stream past its completion deadline retires at the block boundary
    with a partial ``expired=True`` completion whose tokens are a PREFIX of
    the uninterrupted stream (nothing was resampled or reordered)."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(1, seed=9)
    eng = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42))
    rid = eng.submit(p[0], 20, deadline_ms=3)
    comps = {c.request_id: c for c in eng.run()}
    c = comps[rid]
    assert c.expired and c.deadline_missed
    assert 0 < len(c.tokens) < 20
    golden = lm_c.generate(p[0:1], max_new_tokens=20)
    assert c.tokens.tolist() == golden.tokens[0][: len(c.tokens)].tolist()
    assert eng.stats["expired"] == 1
    # the slot is reusable: a follow-up request serves bit-identically
    p2 = _prompts(1, seed=11)
    r2 = eng.submit(p2[0], 5)
    comps = {c.request_id: c for c in eng.run()}
    g2 = lm_c.generate(p2[0:1], max_new_tokens=5)
    assert comps[r2].tokens.tolist() == g2.tokens[0].tolist()


def test_deadline_expires_queued_request_without_burning_prefill(stack):
    """A request whose deadline dies while it queues is expired with ZERO
    tokens and zero inserts spent on it."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(3, seed=13)
    eng = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42))
    for i in range(3):                       # occupy every slot for a while
        eng.submit(p[i], 16)
    eng.step_block()                         # occupants admitted and decoding
    doomed = eng.submit(_prompts(1, seed=15)[0], 4, deadline_ms=2)
    inserts_before = eng.stats["inserts"]
    steps = 0
    while not any(c.request_id == doomed for c in eng.completed):
        assert eng.step_block() and (steps := steps + 1) < 20
    c = [c for c in eng.completed if c.request_id == doomed][0]
    assert c.expired and len(c.tokens) == 0
    assert eng.stats["inserts"] == inserts_before  # no prefill burned on it
    eng.run()


def test_ttft_deadline_expires_mid_chunked_prefill_pages_roll_back(stack):
    """TTFT deadline dies MID-chunked-prefill: the admission unwinds
    atomically (pages released through the cancel machinery), the request
    expires with 0 tokens, and the concurrently-decoding tenant's stream is
    bit-identical to its solo generate."""
    cfg, params, lm_c, lm_p = stack
    p8 = _prompts(1, seed=17)
    p16 = _prompts(1, s=16, seed=19)[0]
    eng = ServeEngine(lm_p, block_steps=K, prefill_chunk_tokens=4,
                      rng=jax.random.key(42))
    tenant = eng.submit(p8[0], 20)
    eng.step_block()                          # tenant mid-admission/decoding
    doomed = eng.submit(p16, 6, ttft_deadline_ms=2)
    comps = {c.request_id: c for c in eng.run()}
    c = comps[doomed]
    assert c.expired and len(c.tokens) == 0
    assert eng.stats["prefill_aborts"] >= 1
    g = lm_c.generate(p8, max_new_tokens=20)
    assert comps[tenant].tokens.tolist() == g.tokens[0].tolist()
    # the abort rolled every held page back: with the tenant retired and
    # the prefix cache drained, the allocator is empty
    pkv = eng.session.paged
    if pkv.prefix is not None:
        pkv.prefix.evict(10 ** 6)
    assert pkv.allocator.in_use() == 0


def test_edf_admission_prefers_earliest_deadline(stack):
    """Deadline-aware admission ordering: with one slot freeing at a time,
    a later-submitted request with a binding deadline is admitted AHEAD of
    an earlier deadline-free request — and both streams stay exact."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(3, seed=21)
    eng = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42))
    # staggered budgets: slots free one at a time
    eng.submit(p[0], 2)
    eng.submit(p[1], 10)
    eng.submit(p[2], 14)
    q_late = eng.submit(_prompts(1, seed=23)[0], 4)                # FIFO
    q_urgent = eng.submit(_prompts(1, seed=25)[0], 4, deadline_ms=60)  # EDF
    comps = {c.request_id: c for c in eng.run()}
    assert comps[q_urgent].queue_blocks < comps[q_late].queue_blocks
    g = lm_c.generate(_prompts(1, seed=23), max_new_tokens=4)
    assert comps[q_late].tokens.tolist() == g.tokens[0].tolist()


# ------------------------------------------------ bounded queue / shedding

def test_bounded_queue_sheds_with_retry_after_then_resubmit_succeeds(stack):
    """The shed-then-resubmit contract: an over-full queue returns a
    structured Rejected with a retry-after estimate; resubmitting the SAME
    prompt after the backlog drains is admitted and served bit-identical to
    its solo generate (fresh request id, deterministic stream)."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(3, seed=27)
    shed_p = _prompts(1, seed=29)[0]
    eng = ServeEngine(lm_c, block_steps=K, max_queue=1,
                      rng=jax.random.key(42))
    for i in range(3):
        eng.submit(p[i], 8)
    eng.step_block()                          # slots full, queue empty
    ok = eng.submit(_prompts(1, seed=31)[0], 4)
    assert isinstance(ok, int)
    rej = eng.submit(shed_p, 4)
    assert isinstance(rej, Rejected)
    assert rej.reason == "queue_full"
    assert rej.retry_after_blocks >= 1 and rej.queue_depth == 1
    assert eng.stats["rejected"] == 1 and len(eng.rejected) == 1
    for _ in range(rej.retry_after_blocks):
        eng.step_block()
    retry = eng.submit(shed_p, 4)
    assert isinstance(retry, int)
    comps = {c.request_id: c for c in eng.run()}
    g = lm_c.generate(shed_p[None], max_new_tokens=4)
    assert comps[retry].tokens.tolist() == g.tokens[0].tolist()


def test_pool_exhausted_shed_reason_and_retry_from_oldest_decoder(stack):
    """ISSUE 7 satellite: a bounded-queue shed forced by PAGE-POOL
    exhaustion (free slots exist, but no pages — previously those free
    slots excused unbounded queueing and the rejection carried only the
    queue-drain estimate) is marked ``reason='pool_exhausted'`` and its
    ``retry_after_blocks`` covers the OLDEST decoding request's remaining
    budget: the earliest retirement that actually returns pages."""
    cfg, params, lm_c, lm_p = stack
    lm_small = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                        max_batch=3, page_size=PAGE,
                        page_pool_pages=12).compile()
    eng = ServeEngine(lm_small, block_steps=K, max_queue=1,
                      rng=jax.random.key(42))
    p = _prompts(3, seed=61)
    r1 = eng.submit(p[0], 12)        # 6 pages: prompt 8 + 12 + K over 4/page
    eng.step_block()                 # r1 decoding; 3 of 9 pool pages free
    assert isinstance(r1, int) and eng.slots.count(None) == 2
    q = eng.submit(p[1], 12)         # needs 6 pages > 3 free: queued
    assert isinstance(q, int)
    rej = eng.submit(p[2], 12)       # backlog at bound, pool can't admit
    assert isinstance(rej, Rejected)
    assert rej.reason == "pool_exhausted"
    # oldest decoder r1 delivered 4 of 12 tokens: 8 remaining = 2 blocks
    expect = -(-(12 - len(eng._out[r1])) // K)
    assert rej.retry_after_blocks >= expect == 2
    # contrast: the same shed on the CONTIGUOUS engine is queue-bound
    eng_c = ServeEngine(lm_c, block_steps=K, max_queue=0,
                        rng=jax.random.key(42))
    for i in range(3):
        eng_c.submit(p[i], 8)
    eng_c.step_block()
    rej_c = eng_c.submit(_prompts(1, seed=63)[0], 8)
    assert isinstance(rej_c, Rejected) and rej_c.reason == "queue_full"
    eng.run()
    eng_c.run()


def test_deadline_shed_policy_evicts_laxest_deadline(stack):
    """shed_policy='deadline': a tight-deadline newcomer displaces the
    deadline-free queued request, which surfaces in engine.rejected."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(3, seed=33)
    eng = ServeEngine(lm_c, block_steps=K, max_queue=1,
                      shed_policy="deadline", rng=jax.random.key(42))
    for i in range(3):
        eng.submit(p[i], 12)
    lax = eng.submit(_prompts(1, seed=35)[0], 4)          # no deadline
    assert isinstance(lax, int)
    urgent = eng.submit(_prompts(1, seed=37)[0], 4, deadline_ms=40)
    assert isinstance(urgent, int)            # admitted: the LAX one shed
    assert eng.stats["shed_evictions"] == 1
    assert [r.request_id for r in eng.rejected] == [lax]
    comps = {c.request_id: c for c in eng.run()}
    assert urgent in comps and lax not in comps


def test_overload_report_surface_and_goodput(stack):
    """run_trace's overload report: with deadlines + a bounded queue at
    ~2x overload, rejections happen, miss rate is populated, and goodput
    counts only in-deadline streams."""
    cfg, params, lm_c, lm_p = stack
    trace = synthetic_trace(10, 128, prompt_lens=(8,), max_new_tokens=8,
                            mean_interarrival_blocks=0.2, deadline_ms=6,
                            seed=3)
    eng = ServeEngine(lm_c, block_steps=K, max_queue=2,
                      shed_policy="deadline", rng=jax.random.key(42))
    rep = run_trace(eng, trace)
    assert rep["max_queue"] == 2 and rep["shed_policy"] == "deadline"
    assert rep["rejected"] + rep["expired"] > 0
    assert rep["deadline_miss_rate"] is not None
    assert 0.0 < rep["deadline_miss_rate"] <= 1.0
    assert rep["goodput_tokens_per_sec"] is not None
    assert rep["goodput_tokens_per_sec"] <= rep["tokens_per_sec"]


# ------------------------------------------------ the recovery oracle

def test_snapshot_restore_bit_identical_matrix(stack):
    """THE acceptance gate: drive 3 blocks, snapshot (through a JSON
    round-trip — the on-disk format), restore into a fresh engine, finish —
    pre-snapshot + post-restore streams equal the uninterrupted oracle for
    every (paged/contiguous × fused/stepwise) restore target, on a workload
    mixing greedy and sampled requests."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_c, submits)
    for name, lm in (("contig", lm_c), ("paged", lm_p)):
        for fused in (True, False):
            eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42))
            for kw in submits:
                eng.submit(**kw)
            for _ in range(2):
                eng.step_block()
            snap = json.loads(json.dumps(eng.snapshot()))
            pre = _streams(eng)
            restored = ServeEngine.from_snapshot(lm, snap, fused=fused)
            assert restored.stats["restored_requests"] >= 1
            restored.run()
            merged = dict(pre)
            merged.update(_streams(restored))
            assert merged == oracle, (name, fused)


def test_snapshot_mid_chunked_prefill_and_queued(stack):
    """Snapshot taken between fused blocks while one request is MID-chunked-
    prefill and another still queued: the restore re-prefills the decoding
    stream, restarts the chunked admission from scratch, keeps the queue —
    and every stream equals the uninterrupted oracle. Allocator drains to 0
    after the restored engine retires everything."""
    cfg, params, lm_c, lm_p = stack
    p8 = _prompts(1, seed=41)
    p16 = _prompts(1, s=16, seed=43)[0]
    submits = [dict(prompt=p8[0], max_new_tokens=9),
               dict(prompt=p16, max_new_tokens=6, arrival_block=1,
                    sampler=Sampler(temperature=1.1)),
               dict(prompt=_prompts(1, seed=45)[0], max_new_tokens=5,
                    arrival_block=4)]
    oracle = _oracle(lm_p, submits, prefill_chunk_tokens=5)
    eng = ServeEngine(lm_p, block_steps=K, prefill_chunk_tokens=5,
                      rng=jax.random.key(42))
    for kw in submits:
        eng.submit(**kw)
    eng.step_block()
    eng.step_block()                          # long prompt now mid-prefill
    assert eng._prefilling, "schedule drifted: expected an in-flight chunk"
    snap = json.loads(json.dumps(eng.snapshot()))
    states = {r["state"] for r in snap["requests"]}
    assert states == {"decoding", "prefill", "queued"}
    pre = _streams(eng)
    restored = ServeEngine.from_snapshot(lm_p, snap)
    restored.run()
    merged = dict(pre)
    merged.update(_streams(restored))
    assert merged == oracle
    pkv = restored.session.paged
    if pkv.prefix is not None:
        pkv.prefix.evict(10 ** 6)
    assert pkv.allocator.in_use() == 0


def test_snapshot_file_roundtrip_and_clean_drain_removes_it(stack, tmp_path):
    """run(snapshot_path=...) writes an atomic snapshot every N blocks and
    removes it on a clean drain; restoring from the file mid-run resumes
    exactly (the runner's crash-recovery CLI contract)."""
    cfg, params, lm_c, lm_p = stack
    path = str(tmp_path / "serve.snap")
    submits = _mixed_submits()
    oracle = _oracle(lm_c, submits)
    eng = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42))
    for kw in submits:
        eng.submit(**kw)
    eng.run(max_blocks=2, snapshot_path=path, snapshot_every_blocks=2)
    import os
    assert os.path.exists(path)               # "crashed" mid-trace
    pre = _streams(eng)
    restored = ServeEngine.from_snapshot(lm_c, path)
    restored.run(snapshot_path=path)
    assert not os.path.exists(path)           # clean drain removed it
    merged = dict(pre)
    merged.update(_streams(restored))
    assert merged == oracle


# ------------------------------------------------ chaos matrix

def _chaos_engine(lm_p, plan_kw=CHAOS_PLAN, **eng_kw):
    # retry budget sized above the plan's worst storm CHAIN (a fresh
    # episode may start on the draw right after one ends) so the storm
    # stays recoverable — the escalation path has its own test below
    return ServeEngine(lm_p, block_steps=K, prefill_chunk_tokens=5,
                       rng=jax.random.key(42), faults=FaultPlan(**plan_kw),
                       dispatch_retries=8, dispatch_backoff_s=0.0,
                       **eng_kw)


def test_chaos_storm_streams_exact_and_allocator_drains(stack):
    """Seeded storms at all three seams (pool exhaustion, transient
    dispatch failures, corrupted pages): the engine completes every
    request without deadlock (bounded blocks), streams equal the NO-FAULT
    oracle bit-for-bit, and after retire-all + prefix eviction the page
    allocator drains to 0 — no leak across abort/retry/replay cycles."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits, prefill_chunk_tokens=5)
    eng = _chaos_engine(lm_p)
    for kw in submits:
        eng.submit(**kw)
    eng.run(max_blocks=300)
    assert not eng.queue and not eng._prefilling and not eng._replay_q
    assert _streams(eng) == oracle
    inj = eng._injector.stats
    assert inj["alloc_faults"] > 0 and inj["dispatch_faults"] > 0, inj
    assert eng.stats["dispatch_retries"] == inj["dispatch_faults"]
    pkv = eng.session.paged
    if pkv.prefix is not None:
        pkv.prefix.evict(10 ** 6)
    assert pkv.allocator.in_use() == 0


def test_chaos_corruption_fires_and_replays_exactly(stack):
    """Drive enough decode blocks that the corruption seam fires from the
    PLAN (not just the public test seam): affected requests re-prefill and
    finish bit-identical to the no-fault oracle."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(2, seed=47)
    submits = [dict(prompt=p[0], max_new_tokens=20),
               dict(prompt=p[1], max_new_tokens=16, arrival_block=1)]
    oracle = _oracle(lm_p, submits)
    eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(42),
                      faults=FaultPlan(seed=5, corrupt_page_prob=0.6))
    for kw in submits:
        eng.submit(**kw)
    eng.run(max_blocks=300)
    assert eng._injector.stats["pages_corrupted"] > 0
    assert eng.stats["corrupt_page_replays"] > 0
    assert _streams(eng) == oracle


def test_fault_plan_replayed_twice_identical(stack):
    """Determinism gate: the same plan over the same trace makes identical
    decisions — completions, engine stats, and injector stats all match."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    runs = []
    for _ in range(2):
        eng = _chaos_engine(lm_p)
        for kw in submits:
            eng.submit(**kw)
        eng.run(max_blocks=300)
        runs.append((_streams(eng), dict(eng.stats),
                     dict(eng._injector.stats)))
    assert runs[0] == runs[1]


def test_injected_page_corruption_physically_garbled_then_replayed(stack):
    """The corruption is REAL: the page's pool bytes are garbled before
    recovery, so the bit-identical final stream proves the replay rewrote
    the K/V (not merely re-pointed tables). Prefix-index entries through
    the bad page are invalidated, so no later sharer splices it in."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(1, seed=49)
    golden = lm_c.generate(p, max_new_tokens=12)
    eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(42))
    rid = eng.submit(p[0], 12)
    eng.step_block()
    slot = next(i for i, r in enumerate(eng.slots) if r is not None)
    victim = eng.session.paged.slot_pages(slot)[0]
    eng.inject_page_corruption([victim])
    assert eng.stats["corrupt_page_replays"] == 1
    comps = {c.request_id: c for c in eng.run()}
    assert comps[rid].tokens.tolist() == golden.tokens[0].tolist()


def test_dispatch_failure_past_retry_budget_escalates(stack):
    """A dispatch that keeps failing past dispatch_retries raises
    DispatchFailed (fail-stop) instead of spinning forever — and the retry
    accounting shows the budget was actually spent."""
    cfg, params, lm_c, lm_p = stack
    eng = ServeEngine(lm_c, block_steps=K, dispatch_retries=2,
                      dispatch_backoff_s=0.0, rng=jax.random.key(42),
                      faults=FaultPlan(seed=0, dispatch_fail_prob=1.0,
                                       dispatch_max_failures=50))
    eng.submit(_prompts(1, seed=51)[0], 4)
    with pytest.raises(DispatchFailed):
        eng.run(max_blocks=10)
    assert eng.stats["dispatch_retries"] == 3  # initial + 2 retries


def test_fault_plan_validation_and_spec_parsing():
    with pytest.raises(ValueError, match="pool_exhaust_prob"):
        FaultPlan(pool_exhaust_prob=1.5)
    with pytest.raises(ValueError, match="storm lengths"):
        FaultPlan(pool_storm_len=0)
    plan = FaultPlan.from_spec(
        '{"seed": 7, "dispatch_fail_prob": 0.5, "dispatch_max_failures": 2}')
    assert plan.seed == 7 and plan.dispatch_fail_prob == 0.5
    assert plan.to_dict()["dispatch_max_failures"] == 2
    with pytest.raises(ValueError, match="JSON object"):
        FaultPlan.from_spec("[1, 2]")


def test_engine_robustness_knob_validation(stack):
    cfg, params, lm_c, lm_p = stack
    with pytest.raises(ValueError, match="shed_policy"):
        ServeEngine(lm_c, block_steps=K, shed_policy="lifo")
    with pytest.raises(ValueError, match="max_queue"):
        ServeEngine(lm_c, block_steps=K, max_queue=-1)
    with pytest.raises(ValueError, match="block_time_ms"):
        ServeEngine(lm_c, block_steps=K, block_time_ms=0.0)
    eng = ServeEngine(lm_c, block_steps=K)
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.submit(_prompts(1)[0], 4, deadline_ms=-1.0)
    with pytest.raises(ValueError, match="page corruption"):
        eng.inject_page_corruption([0])


@pytest.mark.slow  # full chaos matrix: fused × stepwise × chunked ×
# one-shot over two seeds — the tier-1 storm above is the fast subset
def test_chaos_full_matrix_slow(stack):
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    for chunk in (0, 5):
        oracle = _oracle(lm_p, submits, prefill_chunk_tokens=chunk)
        for fused in (True, False):
            for seed in (1, 9):
                plan = dict(CHAOS_PLAN)
                plan["seed"] = seed
                eng = ServeEngine(lm_p, block_steps=K,
                                  prefill_chunk_tokens=chunk, fused=fused,
                                  rng=jax.random.key(42),
                                  faults=FaultPlan(**plan),
                                  dispatch_retries=8,
                                  dispatch_backoff_s=0.0)
                for kw in submits:
                    eng.submit(**kw)
                eng.run(max_blocks=400)
                assert _streams(eng) == oracle, (chunk, fused, seed)
                pkv = eng.session.paged
                if pkv.prefix is not None:
                    pkv.prefix.evict(10 ** 6)
                assert pkv.allocator.in_use() == 0, (chunk, fused, seed)
