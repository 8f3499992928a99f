"""Prefill/decode disaggregation (ISSUE 11 tentpole gates).

THE exactness oracle: a disaggregated fleet — dedicated prefill workers
handing finished KV pages to the decode pool through checksummed
:class:`KVHandoff` buffers — serves token streams BIT-IDENTICAL to a
single ``ServeEngine`` over the same submissions, across fused/stepwise ×
greedy/sampled × prefix-hit/cold, with handoff faults degrading to local
re-prefill (never a wrong token), prefill-worker drains migrating
mid-chunk work atomically, and crashes on either side of the split
failing over exactly. Allocators on every worker drain to 0.

Tier-1 cost discipline: the shared tiny 2-layer module-scoped paged stack
(the sibling serving suites' shapes), short budgets, no new model builds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    DisaggRouter,
    FaultPlan,
    KVHandoff,
    Router,
    Sampler,
    ServeEngine,
    run_disagg_trace,
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
def lm_p():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE).compile()


def _prompts(n, s=8, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _mixed_submits():
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


def _drained_to_zero(router):
    """Every worker's allocator drains to 0 once the prefix cache lets go
    (dead replicas excluded — their pages died with them)."""
    for i, eng in enumerate(router.engines):
        if not router._alive[i]:
            continue
        pkv = eng.session.paged
        if pkv.prefix is not None:
            pkv.prefix.evict(10 ** 6)
        assert pkv.allocator.in_use() == 0, (i, pkv.allocator.in_use())


# ------------------------------------------------ the exactness matrix

def test_disagg_bit_identical_fused_and_stepwise(lm_p):
    """THE acceptance gate: 1 prefill + 1 decode worker serve a
    greedy+sampled staggered workload bit-identical to the single-engine
    oracle, in BOTH decode modes — the split adds a migration, not
    semantics. Every request's pages travel as a handoff."""
    submits = _mixed_submits()
    for fused in (True, False):
        oracle = _oracle(lm_p, submits, fused=fused)
        router = DisaggRouter(lm_p, 2, prefill_replicas=1,
                              rng=jax.random.key(42), block_steps=K,
                              fused=fused)
        for kw in submits:
            router.submit(**kw)
        router.run(max_blocks=300)
        assert _streams(router) == oracle, fused
        assert router.stats["handoffs_sent"] == len(submits)
        assert router.stats["handoffs_adopted"] == len(submits)
        assert router.stats["handoffs_degraded"] == 0
        _drained_to_zero(router)


def test_disagg_prefix_hit_and_cold_exact(lm_p):
    """Prefix-hit × cold admissions stay exact through the split: the
    prefill worker's radix keeps the shared prefix hot (later admissions
    prefill only the suffix before handoff), and adopted pages REGISTER in
    the decode worker's index. Streams equal the single-engine oracle."""
    rs = np.random.RandomState(9)
    prefix = rs.randint(1, 127, (8,)).astype(np.int32)

    def with_prefix(seed):
        tail = np.random.RandomState(seed).randint(1, 127, (8,))
        return np.concatenate([prefix, tail]).astype(np.int32)

    cold = _prompts(1, seed=31)[0]
    submits = [dict(prompt=with_prefix(1), max_new_tokens=8),
               dict(prompt=cold, max_new_tokens=8, arrival_block=2,
                    sampler=Sampler(temperature=1.2)),
               dict(prompt=with_prefix(2), max_new_tokens=6,
                    arrival_block=4)]
    oracle = _oracle(lm_p, submits)
    router = DisaggRouter(lm_p, 2, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K)
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=300)
    assert _streams(router) == oracle
    pre = router.engines[0].session.paged
    dec = router.engines[1].session.paged
    assert pre.stats["prefix_hits"] >= 1          # the radix stayed hot
    assert dec.stats["adopted_pages"] >= 6        # pages arrived via handoff
    assert dec.prefix.cached_pages >= 2           # adopted path registered
    _drained_to_zero(router)


def test_handoff_fault_plan_degrades_exact_and_replays_identical(lm_p):
    """The migrate seam: failed and corrupted handoffs degrade to a local
    re-prefill on the decode side — streams STILL equal the no-fault
    oracle bit-for-bit, the same plan replayed twice makes identical
    decisions, and every allocator drains to 0."""
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits)
    runs = []
    for _ in range(2):
        router = DisaggRouter(
            lm_p, 2, prefill_replicas=1, rng=jax.random.key(42),
            block_steps=K,
            faults=FaultPlan(seed=13, migrate_fail_prob=0.35,
                             migrate_corrupt_prob=0.35))
        for kw in submits:
            router.submit(**kw)
        router.run(max_blocks=300)
        assert _streams(router) == oracle
        assert router.stats["handoffs_degraded"] >= 1
        assert (router.stats["handoffs_adopted"]
                + router.stats["handoffs_degraded"]
                == router.stats["handoffs_sent"])
        inj = router._injector.stats
        assert inj["migrate_faults"] + inj["migrate_corruptions"] \
            == router.stats["handoffs_degraded"]
        _drained_to_zero(router)
        runs.append((_streams(router), dict(router.stats), dict(inj)))
    assert runs[0] == runs[1]


def test_adopt_after_retire_page_reuse(lm_p):
    """Sustained traffic through one decode worker cycles more page
    allocations than the pool holds: adoptions after retirements REUSE
    freed physical pages (stale bytes sit behind the position mask) and
    every stream stays exact."""
    p = _prompts(9, seed=17)
    submits = [dict(prompt=p[i], max_new_tokens=12,
                    arrival_block=i // 3) for i in range(9)]
    oracle = _oracle(lm_p, submits)
    router = DisaggRouter(lm_p, 2, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K)
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=400)
    assert _streams(router) == oracle
    dec = router.engines[1].session.paged
    # footprint cycled through adoption exceeds the pool: reuse happened
    per_req = -(-(8 + 12 + K) // PAGE)
    assert 9 * per_req > dec.capacity_pages()
    assert router.stats["handoffs_adopted"] == 9
    _drained_to_zero(router)


# ------------------------------------------------ drain / failover

def test_drain_prefill_worker_migrates_mid_chunk(lm_p):
    """Satellite gate: draining a prefill worker mid-chunked-prefill
    unwinds the admission atomically (page rollback) and the request
    finishes through ANOTHER prefill worker — zero tokens lost, streams
    equal the oracle, the drained worker parks with a snapshot."""
    p16 = _prompts(1, s=16, seed=23)[0]
    p8 = _prompts(2, seed=25)
    submits = [dict(prompt=p8[0], max_new_tokens=10),
               dict(prompt=p8[1], max_new_tokens=10),
               dict(prompt=p16, max_new_tokens=6,
                    sampler=Sampler(temperature=1.1))]
    oracle = _oracle(lm_p, submits, prefill_chunk_tokens=5)
    router = DisaggRouter(lm_p, 3, prefill_replicas=2,
                          rng=jax.random.key(42), block_steps=K,
                          prefill_chunk_tokens=5)
    for kw in submits:
        router.submit(**kw)
    router.step_block()
    victim = next((i for i in range(2)
                   if router.engines[i]._prefilling), None)
    assert victim is not None, "schedule drifted: no in-flight chunk"
    router.drain(victim)
    router.run(max_blocks=400)
    assert _streams(router) == oracle
    assert router.stats["drains"] == 1
    assert router.stats["drain_migrated_requests"] >= 1
    assert victim in router.snapshots
    states = {s["replica"]: s for s in router.replica_states()}
    assert states[victim]["state"] == "drained"
    assert states[victim]["role"] == "prefill"
    _drained_to_zero(router)


def test_decode_worker_crash_failover_exact(lm_p):
    """A decode worker dies mid-stream: the router's heartbeat failover
    replays its adopted streams onto the surviving decode worker from the
    delivery records (local re-prefill + resume) — bit-identical."""
    p = _prompts(4, seed=11)
    submits = [dict(prompt=p[i], max_new_tokens=24) for i in range(4)]
    oracle = _oracle(lm_p, submits)
    router = DisaggRouter(lm_p, 3, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K,
                          crash_at=[(3, 1)])
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=400)
    assert router.stats["crashes"] == 1
    assert router.stats["failovers"] == 1
    assert router.stats["failed_over_requests"] >= 1
    assert _streams(router) == oracle
    states = {s["replica"]: s for s in router.replica_states()}
    assert states[1]["state"] == "dead"
    _drained_to_zero(router)


def test_prefill_worker_crash_replays_as_fresh_prefill(lm_p):
    """A prefill worker dies mid-chunk: its un-handed-off requests (zero
    delivered tokens) replay as FRESH prefill work on the surviving
    prefill worker — re-prefilled, re-handed-off, bit-identical. A handoff
    already pumped to the router keeps flowing."""
    p16 = _prompts(1, s=16, seed=23)[0]
    p8 = _prompts(2, seed=25)
    submits = [dict(prompt=p16, max_new_tokens=8),
               dict(prompt=p8[0], max_new_tokens=8, arrival_block=1,
                    sampler=Sampler(temperature=0.9))]
    oracle = _oracle(lm_p, submits, prefill_chunk_tokens=5)
    router = DisaggRouter(lm_p, 3, prefill_replicas=2,
                          rng=jax.random.key(42), block_steps=K,
                          prefill_chunk_tokens=5, crash_at=[(1, 0)])
    for kw in submits:
        router.submit(**kw)
    router.run(max_blocks=400)
    assert router.stats["crashes"] == 1
    assert router.stats["failovers"] == 1
    assert _streams(router) == oracle
    _drained_to_zero(router)


# ------------------------------------------------ surface / validation

def test_run_disagg_trace_report_and_lanes(lm_p, tmp_path):
    """The report surface: roles, handoff lifecycle counters, decode-clock
    latency keys; the shared tracer carries migrate:send/recv lanes and
    the exported Chrome trace validates."""
    trace = synthetic_trace(6, 128, prompt_lens=(8,), max_new_tokens=6,
                            mean_interarrival_blocks=0.5, seed=7)
    router = DisaggRouter(lm_p, 2, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K, trace=True)
    rep = run_disagg_trace(router, trace)
    assert rep["disagg"] is True
    assert rep["prefill_replicas"] == 1 and rep["decode_replicas"] == 1
    assert rep["requests_completed"] == 6
    assert rep["handoffs_sent"] == rep["handoffs_adopted"] == 6
    assert rep["handoff_pages"] >= 12
    assert rep["adopted_pages"] == rep["handoff_pages"]
    assert rep["itl_p50_ms_decode_clock"] is not None
    assert rep["itl_p99_ms_decode_clock"] is not None
    assert rep["decode_stall_excess_ms"] is not None
    roles = [s["role"] for s in rep["replica_states"]]
    assert roles == ["prefill", "decode"]
    # the decode contract is untouched: the decode worker's tracer spans
    # show 2 host ops per decode block (adoption rides between blocks)
    from tests.helpers import decode_host_ops_per_block
    assert decode_host_ops_per_block(router.engines[1]) == 2.0
    doc = router.tracer.export_chrome(str(tmp_path / "disagg_trace.json"))
    summary = validate_chrome_trace(doc)
    assert {"migrate_send", "migrate_adopt", "migrate:send",
            "migrate:recv"} <= summary["names"]


def test_disagg_validation_and_role_guards(lm_p):
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm_c = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=2)
    with pytest.raises(ValueError, match="paged"):
        DisaggRouter(lm_c, 2)
    with pytest.raises(ValueError, match="prefill_replicas"):
        DisaggRouter(lm_p, 2, prefill_replicas=2)
    with pytest.raises(ValueError, match="prefill_replicas"):
        DisaggRouter(lm_p, 2, prefill_replicas=0)
    with pytest.raises(ValueError, match="role"):
        DisaggRouter(lm_p, 2, role="decode")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(lm_c, role="prefill")
    with pytest.raises(ValueError, match="role"):
        ServeEngine(lm_p, role="hybrid")
    router = DisaggRouter(lm_p, 2, prefill_replicas=1, block_steps=K)
    with pytest.raises(ValueError, match="multi-LoRA"):
        router.submit(_prompts(1)[0], 4, adapter="a0")
    # role guards at the engine seams
    with pytest.raises(ValueError, match="decode worker"):
        router.engines[1].submit(_prompts(1)[0], 4)
    from neuronx_distributed_tpu.inference import Request
    req = Request(request_id=99, prompt=_prompts(1)[0], max_new_tokens=4)
    with pytest.raises(ValueError, match="prefill worker"):
        router.engines[0].resume(req, [1])
    with pytest.raises(ValueError, match="adopt_handoff"):
        router.engines[0].adopt_handoff(None)
    # a classic Router on the same lm reports role="both"
    plain = Router(lm_p, 1, block_steps=K)
    assert plain.replica_states()[0]["role"] == "both"


def test_kv_handoff_seal_verify_corrupt():
    payload = {"['cached_key']": np.arange(24, dtype=np.float32)}
    from neuronx_distributed_tpu.inference import Request
    req = Request(request_id=0, prompt=np.ones((4,), np.int32),
                  max_new_tokens=4)
    h = KVHandoff(req=req, first_token=3, first_ts=0.0, page_size=4,
                  payloads=[payload]).seal()
    assert h.verify()
    assert h.pages == 1 and h.nbytes() == 96
    assert h.tp_degree == 1    # off-mesh framing records the degree
    h.corrupt()
    assert not h.verify()      # the flip is real and the checksum sees it


def test_adopt_rejects_tp_degree_mismatch(lm_p):
    """ISSUE 16 satellite: a handoff whose framing was sealed under a
    DIFFERENT TP degree is rejected structurally on adopt — degraded to a
    local re-prefill (bit-identical by the rng contract), never written
    into the pool. The rejection is the degree check, not the checksum:
    every forged handoff still verifies clean."""
    submits = _mixed_submits()
    oracle = _oracle(lm_p, submits)
    router = DisaggRouter(lm_p, 2, prefill_replicas=1,
                          rng=jax.random.key(42), block_steps=K)
    dec = router.engines[1]
    orig, verdicts = dec.adopt_handoff, []

    def forge(h):
        assert h.tp_degree == 1        # stamped by the sealing worker
        h.tp_degree = 4                # ...now claim a foreign degree
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
    # "degraded" with clean bytes == the structured cross-degree rejection
    assert verdicts and all(v == ("degraded", True) for v in verdicts)
    _drained_to_zero(router)
