"""Critical-path attribution gates (ISSUE 9 tentpole).

THE invariant: every request's phase decomposition sums EXACTLY to its
end-to-end latency on the virtual block clock — queued / requeue_backoff /
pool_wait / prefill / decode / corrupt_replay / failover_replay are
contiguous, non-overlapping, and complete. Pinned on the plain lanes AND
on the chaos matrix (small pool + host tier + dispatch faults + page
corruption + a replica crash, all in one router run), because the phases
that matter most only exist when things go wrong.

Also here: ``explain_deadline_miss`` (the PROFILE round-10 manual timeline
read, automated — it must name the right culprit phase), the aggregate
``attribution_report`` groupings (per-tenant, per-replica), and the
incident bundles the chaos run dumps along the way.

Tier-1 cost discipline: ONE module-scoped small-pool paged lm (the tier
suite's shapes) serves every test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    FaultPlan,
    Router,
    Sampler,
    ServeEngine,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability import (
    validate_incident_bundle,
)
from neuronx_distributed_tpu.observability.attribution import (
    PHASES,
    attribution_report,
    explain_deadline_miss,
    known_request_ids,
    request_attribution,
)
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4
SMALL_POOL = 13


@pytest.fixture(scope="module")
def lm():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE,
                    page_pool_pages=SMALL_POOL).compile()


def _prompts(n, s=8, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _check_invariant(tracer):
    """The acceptance gate, applied to every request the trace knows."""
    rids = known_request_ids(tracer)
    assert rids, "trace knows no requests"
    atts = {}
    for rid in rids:
        a = request_attribution(tracer, rid)
        assert a is not None, rid
        assert sum(a["phases_blocks"].values()) == a["e2e_blocks"], (rid, a)
        assert set(a["phases_blocks"]) <= set(PHASES), (rid, a)
        # segments are contiguous and cover [origin, end] exactly
        cur = a["origin_block"]
        for seg in a["segments"]:
            assert seg["start_block"] == cur, (rid, a["segments"])
            assert seg["end_block"] > seg["start_block"]
            cur = seg["end_block"]
        assert cur == a["end_block"], (rid, a["segments"])
        # the wall overlay sums to the wall span it decomposed (each phase
        # is rounded to 3 decimals on export, so eps = phases * 0.5e-3)
        assert sum(a["phases_wall_ms"].values()) == pytest.approx(
            a["wall_ms"], abs=5e-3 * max(len(a["phases_wall_ms"]), 1))
        atts[rid] = a
    return atts


# ------------------------------------------------- base lanes + invariant

def test_base_lane_decomposition(lm):
    """Queued wait, chunked prefill, pool-pressure deferral and plain
    decode all land in their named phases, and the invariant holds for
    every request including the pool-deferred ones."""
    eng = ServeEngine(lm, block_steps=K, trace=True, prefill_chunk_tokens=5,
                      rng=jax.random.key(7))
    short = _prompts(5, s=8, seed=3)
    long16 = _prompts(1, s=16, seed=5)[0]
    tiny4 = _prompts(1, s=4, seed=8)[0]
    chunked = eng.submit(long16, 6)              # 16 tokens, C=5: 4 rounds
    inserted = eng.submit(tiny4, 6)              # one-shot (4 <= C)
    queued = [eng.submit(p, 8, arrival_block=1) for p in short[1:]]
    eng.run(max_blocks=300)
    atts = _check_invariant(eng.tracer)

    a = atts[chunked]
    assert a["phases_blocks"].get("prefill", 0) > 0
    assert a["annotations"]["prefill_chunks"] == 4
    assert a["terminal"] == "retire" and not a["in_flight"]
    # one-shot insert: admission and first token share a block, so the
    # prefill phase is zero-width by construction
    assert "prefill" not in atts[inserted]["phases_blocks"]
    assert atts[inserted]["phases_blocks"].get("decode", 0) > 0
    # the backlog paid a queue and/or pool wait (3 slots, 6 requests over
    # a small pool), and whatever it paid is attributed, not lost
    waited = [atts[r] for r in queued]
    assert any(w["phases_blocks"].get("queued", 0)
               + w["phases_blocks"].get("pool_wait", 0) > 0 for w in waited)
    if eng.stats["deferred_admissions"] > 0:
        assert any("pool_wait" in w["phases_blocks"] for w in waited)


def test_one_shot_inserts_wall_time_lands_in_prefill(lm):
    """``admit`` is stamped where the slot was claimed and ``first_token``
    where the token reached the host: the walker charges the insert between
    them to ``prefill`` on the wall clock, nothing to it on the block clock
    (both marks are the same block), and ``queued`` keeps only the wait."""
    eng = ServeEngine(lm, block_steps=K, trace=True, rng=jax.random.key(7))
    first = eng.submit(_prompts(1, s=8, seed=3)[0], 6)
    eng.step_block()
    second = eng.submit(_prompts(1, s=8, seed=4)[0], 6,
                        arrival_block=eng.blocks)     # into the running batch
    eng.run(max_blocks=50)
    atts = _check_invariant(eng.tracer)
    dispatch = [e for e in eng.tracer.events()
                if e["lane"] == (eng.lane, "dispatch")]
    inserts = [e for e in dispatch if e["name"] == "insert"]
    fetches = [e for e in dispatch if e["name"] == "insert_fetch"]
    assert len(inserts) == len(fetches) == 2
    for rid, insert, fetch in zip((first, second), inserts, fetches):
        a = atts[rid]
        assert "prefill" not in a["phases_blocks"]       # block widths as before
        assert a["phases_blocks"] == {"decode": a["e2e_blocks"]}
        wall = a["phases_wall_ms"]
        program_and_fetch = (insert["dur"] + fetch["dur"]) * 1e3
        # the whole insert (its program call and its fetch) and little else
        assert wall["prefill"] >= program_and_fetch - 1e-3
        evs = {e["name"]: e for e in eng.tracer.by_request()[rid]}
        between = (evs["first_token"]["ts"] - evs["admit"]["ts"]) * 1e3
        assert wall["prefill"] == pytest.approx(between, abs=2e-3)
        # queued: submit to the claim, which is before the insert began
        queued = evs["queued"]
        assert queued["ts"] + queued["dur"] <= insert["ts"]
        assert wall.get("queued", 0.0) == pytest.approx(queued["dur"] * 1e3,
                                                        abs=2e-3)


def test_attribution_empty_without_tracing(lm):
    eng = ServeEngine(lm, block_steps=K)
    eng.submit(_prompts(1)[0], 4)
    eng.run()
    assert eng.request_attribution(0) is None
    assert eng.attribution_report() == {"requests": 0}


# ------------------------------------------------- explain_deadline_miss

def test_explain_deadline_miss_names_queued_burn(lm):
    """Round-10's conclusion ('the budget died in the queue') must come
    out of the automated read: overload a 3-slot pool so queued requests
    expire, then ask."""
    eng = ServeEngine(lm, block_steps=K, trace=True, rng=jax.random.key(3))
    p = _prompts(6, s=8, seed=9)
    ids = [eng.submit(pr, 10, ttft_deadline_ms=3.0, deadline_ms=30.0)
           for pr in p]
    comps = {c.request_id: c for c in eng.run(max_blocks=300)}
    expired = [r for r in ids if comps[r].expired]
    served = [r for r in ids if not comps[r].deadline_missed
              and not comps[r].expired]
    assert expired, "overload failed to expire anyone"
    ex = eng.explain_deadline_miss(expired[0])
    assert ex["missed"] and ex["kind"] == "ttft"
    # the budget died waiting for admission — queue depth or pool pressure,
    # whichever this pool hit first; either way the culprit is named
    assert ex["culprit_phase"] in ("queued", "pool_wait")
    assert ex["culprit_phase"] in ex["narrative"]
    assert ex["attribution"]["e2e_blocks"] >= ex["budget_blocks"]
    # a request that met its deadline explains as not-missed
    if served:
        ok = eng.explain_deadline_miss(served[0])
        assert ok["missed"] is False and "attribution" in ok
    # unknown id degrades gracefully
    assert "error" in eng.explain_deadline_miss(10 ** 6)


# ---------------------------------------------------- the chaos matrix

def test_chaos_matrix_attribution_invariant_and_incidents(lm, tmp_path):
    """THE acceptance gate: faults + tier + failover in one router run —
    dispatch faults retried, a replica crashing mid-decode with its
    streams failing over, pool pressure spilling into the host tier — and
    EVERY request's phase decomposition still sums to its end-to-end
    latency, with the failover price showing up as its own phase. The
    flight recorder armed on the same run dumps schema-valid bundles."""
    router = Router(
        lm, 2, rng=jax.random.key(42), block_steps=K, trace=True,
        host_tier_pages=24, crash_at=[(2, 1)],
        incident_dir=str(tmp_path / "bundles"),
        faults=FaultPlan(seed=3, dispatch_fail_prob=0.15,
                         dispatch_max_failures=1))
    rs = np.random.RandomState(1)
    prefix = rs.randint(1, 127, (8,)).astype(np.int32)
    for i in range(8):
        tail = rs.randint(1, 127, (8,)).astype(np.int32)
        router.submit(np.concatenate([prefix, tail]), 18,
                      arrival_block=i // 2, tenant=f"t{i % 2}",
                      sampler=Sampler(temperature=1.1) if i % 3 == 2
                      else None)
    router.run(max_blocks=400)
    assert router.stats["crashes"] == 1
    assert router.stats["failed_over_requests"] > 0
    assert sum(e.stats["dispatch_retries"]
               for e in router.engines) > 0        # faults really fired
    atts = _check_invariant(router.tracer)
    assert len(atts) == 8
    assert any(a["phases_blocks"].get("failover_replay", 0) > 0
               for a in atts.values()), "no request paid a failover phase"
    # aggregate report: groupings present, request counts consistent
    rep = router.attribution_report()
    assert rep["requests"] == 8
    assert set(rep["per_tenant"]) == {"t0", "t1"}
    assert sum(g["requests"] for g in rep["per_tenant"].values()) == 8
    assert "failover_replay" in rep["phases_blocks"]
    total = sum(v["total"] for v in rep["phases_blocks"].values())
    assert total == sum(a["e2e_blocks"] for a in atts.values())
    # incident bundles: at least the replica crash, every file schema-valid
    bundles = router.incident.bundles
    assert bundles
    kinds = set()
    for b in bundles:
        s = validate_incident_bundle(b)
        kinds.add(s["kind"])
        assert s["events"] > 0
    assert "replica_crash" in kinds


def test_disagg_migration_phase_invariant(lm):
    """ISSUE 11 satellite: the ``migration`` phase — the handoff span
    between prefill-done (``migrate_send``) and decode-adopt
    (``migrate_adopt``, or the ``replay_admit`` a degraded handoff resumes
    through) — closes the sum(phases)==e2e invariant on a disaggregated
    chaos run: a small decode pool defers adoptions (nonzero migration
    width) while the migrate fault seam degrades others to local
    re-prefill."""
    from neuronx_distributed_tpu.inference import DisaggRouter

    router = DisaggRouter(
        lm, 2, prefill_replicas=1, rng=jax.random.key(42), block_steps=K,
        trace=True,
        faults=FaultPlan(seed=5, migrate_fail_prob=0.3,
                         migrate_corrupt_prob=0.2))
    rs = np.random.RandomState(3)
    prefix = rs.randint(1, 127, (8,)).astype(np.int32)
    for i in range(6):
        tail = rs.randint(1, 127, (8,)).astype(np.int32)
        router.submit(np.concatenate([prefix, tail]), 12,
                      arrival_block=i // 2, tenant=f"t{i % 2}",
                      sampler=Sampler(temperature=1.1) if i % 3 == 2
                      else None)
    router.run(max_blocks=400)
    assert router.stats["handoffs_sent"] == 6
    assert router.stats["handoffs_degraded"] >= 1, "seam never fired"
    atts = _check_invariant(router.tracer)
    assert len(atts) == 6
    assert any(a["phases_blocks"].get("migration", 0) > 0
               for a in atts.values()), "no request paid a migration phase"
    # degraded handoffs are annotated on the request they hit, and their
    # whole send→resume gap is charged to migration (never lost)
    degraded = [a for a in atts.values()
                if a["annotations"]["migrate_degrades"] > 0]
    assert degraded
    assert all(a["phases_blocks"].get("migration", 0) > 0
               for a in degraded)
    rep = attribution_report(router.tracer)
    assert "migration" in rep["phases_blocks"]
    assert rep["phases_blocks"]["migration"]["total"] == sum(
        a["phases_blocks"].get("migration", 0) for a in atts.values())


def test_attribution_matches_run_trace_queue_accounting(lm):
    """Cross-check against the engine's own completion bookkeeping: the
    attribution's queued+pool_wait blocks equal the Completion's
    queue_blocks for every admitted-from-queue request (two independent
    derivations of the same quantity)."""
    eng = ServeEngine(lm, block_steps=K, trace=True, rng=jax.random.key(5))
    p = _prompts(6, s=8, seed=4)
    ids = [eng.submit(pr, 6, arrival_block=i) for i, pr in enumerate(p)]
    comps = {c.request_id: c for c in eng.run(max_blocks=300)}
    for rid in ids:
        a = request_attribution(eng.tracer, rid)
        waited = (a["phases_blocks"].get("queued", 0)
                  + a["phases_blocks"].get("pool_wait", 0))
        assert waited == comps[rid].queue_blocks, rid
