"""Multi-replica front door (ISSUE 7 tentpole gates).

Four acceptance surfaces:

* the IDENTITY ORACLE — a Router over N=1 replica serves token streams
  bit-identical to a bare ``ServeEngine`` (fused/stepwise × greedy/sampled):
  the front door adds placement, not semantics;
* the FAILOVER ORACLE — with a replica crashing mid-decode (scheduled or
  seeded plan), every affected request's stream equals the no-fault
  single-replica oracle bit-for-bit (token t of request r draws
  ``fold_in(fold_in(base, r), t)`` regardless of which replica serves it),
  and the surviving replicas' allocators drain to 0;
* DRAIN under load loses zero tokens — queued/mid-prefill work migrates
  (atomic page rollback), decoding streams finish, the drained replica
  parks with a snapshot;
* FAIRNESS — weighted fair queueing holds a compliant tenant's service
  share near its quota against a 10:1 offered-load burst, and tenant-aware
  shedding evicts the over-budget tenant's tail first.

Tier-1 cost discipline: the shared tiny 2-layer module-scoped stack
(the sibling serving suites' shapes); the full chaos matrix is
``@pytest.mark.slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    FaultPlan,
    Rejected,
    Router,
    Sampler,
    ServeEngine,
    run_router_trace,
)
from neuronx_distributed_tpu.inference.replay import synthetic_trace
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability import validate_chrome_trace
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
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _mixed_submits():
    """Greedy + sampled + staggered arrivals — the matrix workload."""
    p = _prompts(3, seed=5)
    return [dict(prompt=p[0], max_new_tokens=12),
            dict(prompt=p[1], max_new_tokens=8, arrival_block=1,
                 sampler=Sampler(temperature=1.3)),
            dict(prompt=p[2], max_new_tokens=10, arrival_block=1,
                 sampler=Sampler(temperature=0.8))]


def _streams(obj):
    return {c.request_id: c.tokens.tolist() for c in obj.completed}


def _oracle(lm, submits, **eng_kw):
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42), **eng_kw)
    for kw in submits:
        eng.submit(**kw)
    eng.run()
    return _streams(eng)


def _drain_allocators(router):
    for eng in router.engines:
        pkv = getattr(eng.session, "paged", None)
        if pkv is None:
            continue
        if pkv.prefix is not None:
            pkv.prefix.evict(10 ** 6)
        yield eng, pkv


# ------------------------------------------------ N=1 identity oracle

def test_router_n1_bit_identical_to_bare_engine(stack):
    """The front-door identity gate: Router(N=1) == bare ServeEngine for
    every (fused/stepwise × contiguous/paged) mode on a greedy+sampled
    workload — placement adds no semantics."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    for lm in (lm_c, lm_p):
        for fused in (True, False):
            oracle = _oracle(lm, submits, fused=fused)
            router = Router(lm, 1, rng=jax.random.key(42), block_steps=K,
                            fused=fused)
            for kw in submits:
                router.submit(**kw)
            router.run()
            assert _streams(router) == oracle, (lm.paged, fused)


# ------------------------------------------------ failover oracle

def test_scheduled_crash_mid_decode_failover_bit_identical(stack):
    """THE failover acceptance gate: replica 0 goes dark mid-decode; the
    router detects the heartbeat silence, fails its in-flight streams over
    to replica 1 from the router-side (prompt, generated) records, and
    every stream — greedy AND sampled — equals the no-fault single-replica
    oracle bit-for-bit. Survivor allocators drain to 0."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits)
    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K,
                    crash_at=[(3, 0)])
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=300)
    assert router.stats["crashes"] == 1
    assert router.stats["failovers"] == 1
    assert router.stats["failed_over_requests"] >= 1
    assert router.last_failover_ms is not None
    assert _streams(router) == oracle
    # the dead replica is out of rotation; the survivor drained cleanly
    states = {s["replica"]: s["state"] for s in router.replica_states()}
    assert states[0] == "dead" and states[1] == "live"
    for eng, pkv in _drain_allocators(router):
        if eng is router.engines[1]:
            assert pkv.allocator.in_use() == 0


def test_failover_from_snapshot_when_router_keeps_no_records(stack):
    """The other recovery source: with ``record_streams=False`` the router
    replays from the crashed replica's last snapshot
    (``snapshot_every_blocks``) — still bit-identical: a replay from an
    OLDER point regenerates the same deterministic prefix."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits)
    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K,
                    crash_at=[(4, 0)], record_streams=False,
                    snapshot_every_blocks=2)
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=300)
    assert router.stats["failovers"] == 1
    assert router.stats["snapshots_taken"] >= 2
    assert _streams(router) == oracle


def test_seeded_plan_crash_replayed_twice_identical(stack):
    """The replica-crash seam is plan-driven and deterministic: the same
    ``FaultPlan(replica_crash_prob=...)`` over the same trace crashes the
    same replica at the same block twice in a row — completions, router
    stats, and injector stats all match, and streams equal the no-fault
    oracle."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits)
    runs = []
    for _ in range(2):
        router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K,
                        faults=FaultPlan(seed=11, replica_crash_prob=0.35))
        for kw in submits:
            router.submit(**kw)
        router.run(max_blocks=300)
        assert router._injector.stats["replica_crashes"] == 1
        assert _streams(router) == oracle
        runs.append((_streams(router), dict(router.stats),
                     dict(router._injector.stats)))
    assert runs[0] == runs[1]


# ------------------------------------------------ graceful drain

def test_drain_under_load_loses_zero_tokens(stack):
    """Rolling-restart primitive: drain a replica while it holds queued +
    decoding work. Queued work migrates to the peer, decoding streams
    finish in place, the drained replica parks WITH a snapshot and an
    empty allocator — and the merged streams equal the no-drain oracle
    (zero tokens lost, zero resampled)."""
    cfg, params, lm_c, lm_p = stack
    p = _prompts(8, seed=21)
    submits = [dict(prompt=p[i], max_new_tokens=8 + (i % 3))
               for i in range(8)]
    oracle = _oracle(lm_p, submits)
    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K)
    for kw in submits:
        router.submit(**kw)
    router.step_block()            # both replicas now hold live streams
    router.drain(0)
    router.run(max_blocks=300)
    assert _streams(router) == oracle
    assert router.stats["drains"] == 1
    assert router.last_drain_ms is not None
    assert 0 in router.snapshots   # the restart artifact
    assert router.snapshots[0]["requests"] == []   # fully drained
    states = {s["replica"]: s["state"] for s in router.replica_states()}
    assert states[0] == "drained"
    for eng, pkv in _drain_allocators(router):
        assert pkv.allocator.in_use() == 0
    # placement never touched the draining replica again
    eng0 = router.engines[0]
    assert not eng0.queue and not eng0.has_decode_work()


def test_drain_migrates_mid_chunked_prefill_atomically(stack):
    """Drain while a long prompt is MID-chunked-prefill on the draining
    replica: the admission unwinds atomically (pages rolled back) and the
    request finishes on the peer — stream bit-identical, no page leak."""
    cfg, params, lm_c, lm_p = stack
    p16 = _prompts(1, s=16, seed=23)[0]
    p8 = _prompts(2, seed=25)
    submits = [dict(prompt=p8[0], max_new_tokens=10),
               dict(prompt=p8[1], max_new_tokens=10),
               dict(prompt=p16, max_new_tokens=6,
                    sampler=Sampler(temperature=1.1))]
    oracle = _oracle(lm_p, submits, prefill_chunk_tokens=5)
    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K,
                    prefill_chunk_tokens=5, placement="round_robin")
    for kw in submits:
        router.submit(**kw)
    router.step_block()
    victim = next((i for i, eng in enumerate(router.engines)
                   if eng._prefilling), None)
    assert victim is not None, "schedule drifted: no in-flight chunk"
    router.drain(victim)
    router.run(max_blocks=300)
    assert _streams(router) == oracle
    assert router.stats["drain_migrated_requests"] >= 1
    for eng, pkv in _drain_allocators(router):
        assert pkv.allocator.in_use() == 0


# ------------------------------------------------ fairness / tenants

def test_wfq_share_within_10pct_at_10to1_offered_load(stack):
    """The fairness unit: two equal-weight tenants offer 10:1 load into a
    saturated fleet. While BOTH are backlogged, WFQ must split delivered
    tokens ~50:50 (each within 10% of quota) — FIFO would give the burst
    ~10/11 of the fleet."""
    cfg, params, lm_c, lm_p = stack
    router = Router(lm_c, 2, rng=jax.random.key(42), block_steps=K,
                    trace=True)
    big = _prompts(30, seed=27)
    small = _prompts(3, seed=29)
    for i in range(30):
        router.submit(big[i], 8, tenant="burst")
    for i in range(3):
        router.submit(small[i], 8, tenant="compliant")
    router.run()
    comps = router.completed
    assert len(comps) == 33
    # the compliant tenant's offer is far below its 50% quota, so it must
    # be served as-if-alone: its last completion lands in the first third
    # of the timeline (FIFO would queue it behind ~27 burst requests)
    done_block = {c.request_id: c.ttft_blocks + c.decode_blocks
                  for c in comps}
    by_tenant = {}
    for c in comps:
        by_tenant.setdefault(c.tenant, []).append(c)
    last_compliant = max(c.ttft_blocks for c in by_tenant["compliant"])
    assert last_compliant <= max(
        c.ttft_blocks for c in by_tenant["burst"]) / 3
    # and while both tenants were backlogged, the share was ~quota: count
    # tokens delivered up to the block the compliant tenant finished
    tok_blocks = {}
    for rid, evs in router.tracer.by_request().items():
        tok_blocks[rid] = [ev["block"] for ev in evs
                           if ev["name"] == "tok"]
    # ... strictly BEFORE the compliant tenant's last retirement block:
    # once its backlog is empty the burst rightly absorbs the whole fleet
    cutoff = max(done_block[c.request_id] for c in by_tenant["compliant"])
    tenant_of = {c.request_id: c.tenant for c in comps}
    share = {"burst": 0, "compliant": 0}
    for rid, blocks in tok_blocks.items():
        t = tenant_of.get(rid)
        if t is not None:
            share[t] += sum(1 for b in blocks if b < cutoff)
    total = share["burst"] + share["compliant"]
    assert total > 0
    frac = share["compliant"] / total
    assert 0.4 <= frac <= 0.6, share


def test_tenant_weights_skew_service_share(stack):
    """Weights bite: at 2:1 weights over two saturating tenants, the heavy
    tenant's head-of-line requests admit strictly earlier on average."""
    cfg, params, lm_c, lm_p = stack
    router = Router(lm_c, 2, rng=jax.random.key(42), block_steps=K,
                    tenant_weights={"gold": 2.0, "std": 1.0})
    g = _prompts(8, seed=31)
    s = _prompts(8, seed=33)
    for i in range(8):
        router.submit(g[i], 8, tenant="gold")
        router.submit(s[i], 8, tenant="std")
    router.run()
    by_tenant = {}
    for c in router.completed:
        by_tenant.setdefault(c.tenant, []).append(c.ttft_blocks)
    assert np.mean(by_tenant["gold"]) < np.mean(by_tenant["std"])


def test_tenant_aware_shed_evicts_over_budget_tail(stack):
    """max_pending overflow sheds from the tenant FURTHEST over its
    weighted backlog share, newest first — the compliant tenant's requests
    never shed while the burst is over budget."""
    cfg, params, lm_c, lm_p = stack
    router = Router(lm_c, 2, rng=jax.random.key(42), block_steps=K,
                    max_pending=4)
    big = _prompts(16, seed=35)
    small = _prompts(2, seed=37)
    rids = [router.submit(big[i], 8, tenant="burst") for i in range(14)]
    shed_burst = [r for r in rids if isinstance(r, Rejected)]
    ok_small = [router.submit(small[i], 8, tenant="compliant")
                for i in range(2)]
    assert all(isinstance(r, int) for r in ok_small)
    assert shed_burst, "burst overflow must shed"
    rej = shed_burst[0]
    assert rej.reason == "tenant_over_budget"
    assert rej.retry_after_blocks >= 1
    # the compliant newcomers displaced burst TAIL entries, not each other
    assert all(router._tenant_of[r.request_id] == "burst"
               for r in router.rejected)
    router.run()
    comp = {c.request_id for c in router.completed}
    assert all(r in comp for r in ok_small)


def test_run_router_trace_reports_per_tenant_surface(stack):
    """run_router_trace: Zipf-skewed tenants ride the trace, the report
    carries the per-tenant p99 ITL/TTFT/goodput table plus the router
    surface (placements, replica states)."""
    cfg, params, lm_c, lm_p = stack
    trace = synthetic_trace(10, 128, prompt_lens=(8,), max_new_tokens=6,
                            mean_interarrival_blocks=0.3, tenants=3,
                            tenant_skew=1.5, seed=7)
    assert {t for item in trace for t in [item["tenant"]]} > {"t0"}
    counts = {}
    for item in trace:
        counts[item["tenant"]] = counts.get(item["tenant"], 0) + 1
    assert counts["t0"] == max(counts.values())   # Zipf head is heaviest
    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K)
    rep = run_router_trace(router, trace)
    assert rep["replicas"] == 2 and rep["requests_completed"] == 10
    assert rep["placements"] == 10
    assert len(rep["replica_states"]) == 2
    per = rep["per_tenant"]
    assert set(per) == set(counts)
    for t, row in per.items():
        assert row["requests"] == counts[t]
        assert row["generated_tokens"] == counts[t] * 6
        assert row["goodput_tokens_per_sec"] is not None


# ------------------------------------------------ placement

def test_prefix_affinity_routes_to_hot_replica(stack):
    """Prefix-affinity placement: after a shared-prefix request lands on
    one replica, later requests with the same prefix follow it (radix
    reuse concentrates instead of smearing) — and prefix_peek probes are
    read-only (no stats, no holds)."""
    cfg, params, lm_c, lm_p = stack
    rs = np.random.RandomState(9)
    prefix = rs.randint(1, 127, (8,)).astype(np.int32)

    def with_prefix(seed):
        tail = np.random.RandomState(seed).randint(1, 127, (8,))
        return np.concatenate([prefix, tail]).astype(np.int32)

    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K)
    first = router.submit(with_prefix(1), 8)
    router.step_block()
    home = router._records[first].replica
    assert home is not None
    pkv_home = router.engines[home].session.paged
    q_before = pkv_home.stats["prefix_queries"]
    # run the first request to completion so its pages are registered
    router.run()
    assert pkv_home.prefix_peek(with_prefix(2).tolist()) == 8
    assert pkv_home.stats["prefix_queries"] == q_before  # peek is free
    followers = [router.submit(with_prefix(s), 4) for s in (2, 3)]
    router.run()
    for rid in followers:
        comp = [c for c in router.completed if c.request_id == rid]
        assert comp and len(comp[0].tokens) == 4
    # both followers were placed on the hot replica
    assert router.stats["affinity_placements"] == 2
    other = router.engines[1 - home].session.paged
    assert other.stats["prefix_hits"] == 0


def test_round_robin_spreads_and_identity_holds(stack):
    """The bench baseline: round_robin alternates replicas and still
    serves bit-identical streams (placement is semantics-free)."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_c, submits)
    router = Router(lm_c, 2, rng=jax.random.key(42), block_steps=K,
                    placement="round_robin")
    for kw in submits:
        router.submit(**kw)
    router.run()
    assert _streams(router) == oracle
    placed = [s["inserted_requests"] for s in router.replica_states()]
    assert all(n >= 1 for n in placed)


# ------------------------------------------------ observability

def test_router_trace_lanes_validate(stack, tmp_path):
    """The shared tracer carries router lanes (place/faults/drain spans)
    AND per-replica engine lanes — the exported Chrome trace validates and
    names every process group."""
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    router = Router(lm_p, 2, rng=jax.random.key(42), block_steps=K,
                    trace=True, crash_at=[(3, 0)])
    for kw in submits:
        router.submit(**kw)
    router.step_block()
    router.run(max_blocks=300)
    doc = router.tracer.export_chrome(str(tmp_path / "router_trace.json"))
    summary = validate_chrome_trace(doc)
    assert {"router", "replica0", "replica1", "req"} <= set(
        summary["processes"])
    names = summary["names"]
    assert {"route_submit", "place", "fault:replica_crash",
            "heartbeat_miss", "failover"} <= names
    # per-replica queue-depth counter tracks ride the replica lanes
    lanes = {ev["lane"] for ev in router.tracer.events()
             if ev["name"] == "queue_depth"}
    assert ("replica1", "queue") in lanes
    # tenant-labeled metric families exist on the router registry
    prom = router.metrics.to_prometheus()
    assert "router_tenant_requests_total" in prom
    assert 'tenant="default"' in prom


def test_router_knob_validation(stack):
    cfg, params, lm_c, lm_p = stack
    with pytest.raises(ValueError, match="num_replicas"):
        Router(lm_c, 0)
    with pytest.raises(ValueError, match="placement"):
        Router(lm_c, 1, placement="random")
    with pytest.raises(ValueError, match="heartbeat_miss_blocks"):
        Router(lm_c, 1, heartbeat_miss_blocks=0)
    with pytest.raises(ValueError, match="max_pending"):
        Router(lm_c, 1, max_pending=-1)
    with pytest.raises(ValueError, match="unknown replica"):
        Router(lm_c, 2, crash_at=[(3, 5)])
    with pytest.raises(ValueError, match="replica_crash_prob"):
        FaultPlan(replica_crash_prob=1.5)
    router = Router(lm_c, 2, block_steps=K)
    with pytest.raises(ValueError, match="unknown replica"):
        router.drain(7)
    with pytest.raises(ValueError, match="weight"):
        router.set_tenant_weight("t", 0.0)


# ------------------------------------------------ engine rejection metadata
# (ISSUE 7 satellite: retry_after on pool-exhausted sheds lives with the
# engine suites in test_serving_faults.py; the router-side contract —
# capped re-queue honoring retry_after — is covered here)

def test_router_honors_engine_rejection_with_capped_requeue(stack):
    """A replica's bounded queue bounces a placement: the router re-queues
    with the verdict's retry_after backoff instead of dropping, and the
    request completes exactly once the backlog drains."""
    cfg, params, lm_c, lm_p = stack
    router = Router(lm_c, 1, rng=jax.random.key(42), block_steps=K,
                    max_queue=1, replica_queue_depth=2)
    p = _prompts(6, seed=41)
    rids = [router.submit(p[i], 6) for i in range(6)]
    assert all(isinstance(r, int) for r in rids)
    router.run(max_blocks=300)
    assert router.stats["requeues"] >= 1
    comp = {c.request_id for c in router.completed}
    assert comp == set(rids)    # nothing dropped
    g = {c.request_id: c.tokens.tolist() for c in router.completed}
    solo = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42))
    for i in range(6):
        solo.submit(p[i], 6)
    solo.run()
    assert g == _streams(solo)


# ------------------------------------------------ chaos matrix (slow)

@pytest.mark.slow  # full chaos: crashes + engine seams × paged, two seeds
def test_router_chaos_full_matrix_slow(stack):
    cfg, params, lm_c, lm_p = stack
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits, prefill_chunk_tokens=5)
    for seed in (1, 9):
        router = Router(
            lm_p, 3, rng=jax.random.key(42), block_steps=K,
            prefill_chunk_tokens=5,
            faults=FaultPlan(seed=seed, replica_crash_prob=0.2,
                             pool_exhaust_prob=0.15, pool_storm_len=2,
                             dispatch_fail_prob=0.1,
                             dispatch_max_failures=2),
            dispatch_retries=8, dispatch_backoff_s=0.0)
        for kw in submits:
            router.submit(**kw)
        router.run(max_blocks=500)
        assert _streams(router) == oracle, seed
        for eng, pkv in _drain_allocators(router):
            if router._alive[router.engines.index(eng)]:
                assert pkv.allocator.in_use() == 0, seed


# ------------------------------------------- request_timeline (ISSUE 9)

def test_request_timeline_and_attribution_cover_failover_replay(stack):
    """ISSUE 9 satellite: the PR 7 failover lane is visible from the
    request's own timeline — a stream that died with its replica shows
    pre-crash tokens, then ``replay_admit`` (``resumed_at`` = tokens
    already delivered) on the survivor, then the resumed stream and a
    clean retire; the attribution layer charges the gap to a
    ``failover_replay`` phase whose width closes the invariant."""
    cfg, params, lm_c, lm_p = stack
    router = Router(lm_c, 2, rng=jax.random.key(42), block_steps=K,
                    trace=True, crash_at=[(2, 1)])
    p = _prompts(4, seed=11)
    for i in range(4):
        router.submit(p[i], 24)
    router.run(max_blocks=300)
    assert router.stats["crashes"] == 1
    assert router.stats["failed_over_requests"] > 0
    replayed = [rid for rid, evs in router.tracer.by_request().items()
                if any(e["name"] == "replay_admit" for e in evs)]
    assert replayed, "no request replayed mid-stream"
    rid = replayed[0]
    # the timeline resolves through ANY engine sharing the tracer
    tl = router.engines[0].request_timeline(rid)
    names = [e["name"] for e in tl]
    i_replay = names.index("replay_admit")
    assert "tok" in names[:i_replay], "no pre-crash deliveries recorded"
    assert "tok" in names[i_replay:] and names[-1] == "retire"
    resumed_at = tl[i_replay]["args"]["resumed_at"]
    assert resumed_at > 0
    # pre-crash token count == the resume index (nothing lost, nothing
    # double-counted on the lane)
    assert names[:i_replay].count("tok") == resumed_at
    att = router.request_attribution(rid)
    assert att["phases_blocks"].get("failover_replay", 0) > 0
    assert sum(att["phases_blocks"].values()) == att["e2e_blocks"]
    # the failover price lands in the aggregate phase mix too
    rep = router.attribution_report()
    assert rep["phases_blocks"]["failover_replay"]["total"] > 0
    # a cleanly-served failover is not a deadline story
    ex = router.explain_deadline_miss(rid)
    assert ex["missed"] is False
