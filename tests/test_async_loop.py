"""Async double-buffered block loop (ISSUE 19 tentpole gates).

``ServeEngine(async_loop=True)`` dispatches block t+1 BEFORE fetching
block t, overlapping the whole host scheduling pass with device
execution. The claim is exactness, not just speed: every test here pins
the async loop's token streams BIT-IDENTICAL to the synchronous loop's
(the retained oracle) across the matrix that has broken pipelined
engines elsewhere — paged/contiguous, greedy/sampled, chunked prefill,
dispatch-fault retry, corrupt-page replay,
snapshot-mid-run, cancel, deadline expiry, disagg adoption — plus the
contract the loop exists for: the tracer-measured device idle between
consecutive blocks is exactly zero (dispatch t+1 precedes fetch t), and
the ≤2-host-ops-per-block accounting is unchanged.

What is and is NOT pinned: stream CONTENT (tokens, finish reasons) is
bit-identical by construction — every scheduling decision commits on the
virtual block clock, never on the in-flight block's values. The block
SCHEDULE may lag by exactly one block (a finished row retires after the
pipelined harvest, one iteration later than sync), so per-request
decode_blocks/total blocks are deliberately not compared.

Tier-1 cost discipline: ONE module-scoped weight set builds the
contiguous, paged and grammar lms (block_steps=4 — the session program
tier-1 already compiles); the sim-mode matrix costs zero XLA.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.disagg import DisaggRouter
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.inference.faults import FaultPlan
from neuronx_distributed_tpu.inference.router import Router
from neuronx_distributed_tpu.inference.simlm import SimCausalLM
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability.tracer import interblock_gaps
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4


@pytest.fixture(scope="module")
def base():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return cfg, params


@pytest.fixture(scope="module")
def lm_c(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3).compile()


@pytest.fixture(scope="module")
def lm_p(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE).compile()


def _prompts(n, s=8, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _mixed_submits(seed=2):
    """Greedy + two sampled temperatures + an EOS row, staggered — the
    matrix workload (samplers exercise the per-request rng fold-in, the
    EOS row exercises the device-carried done latch mid-pipeline)."""
    p = _prompts(4, seed=seed)
    return [dict(prompt=p[0], max_new_tokens=9),
            dict(prompt=p[1], max_new_tokens=7, arrival_block=1,
                 sampler=Sampler(temperature=0.8)),
            dict(prompt=p[2], max_new_tokens=12, eos_token_id=7,
                 arrival_block=2),
            dict(prompt=p[3], max_new_tokens=6, arrival_block=3,
                 sampler=Sampler(temperature=1.3))]


def _streams(obj):
    return {c.request_id: (c.tokens.tolist(), c.finish_reason)
            for c in obj.completed}


def _run(lm, async_loop, submits, **eng_kw):
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42),
                      async_loop=async_loop, **eng_kw)
    for kw in submits:
        eng.submit(**kw)
    eng.run()
    return eng


# --------------------------------------------------------------------------
# the exactness matrix: async == sync bit-for-bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["contig", "paged", "paged_chunked"])
def test_async_matches_sync_matrix(lm_c, lm_p, mode):
    """fused × paged/contig × greedy/sampled × EOS × chunked prefill:
    async streams equal the sync oracle's token for token."""
    lm = lm_c if mode == "contig" else lm_p
    kw = dict(prefill_chunk_tokens=5) if mode == "paged_chunked" else {}
    sync = _streams(_run(lm, False, _mixed_submits(), **kw))
    eng = _run(lm, True, _mixed_submits(), **kw)
    assert _streams(eng) == sync
    # the pipeline actually pipelined (depth reached 1 in steady state)
    assert eng.stats["decode_blocks"] > 0
    assert not eng._inflight and not eng._first_pending


def test_async_dispatch_fault_retry_exact(lm_p):
    """A failed async dispatch surfaces AT the dispatch call (the args —
    including the donated cache — are untouched until the injector lets
    the program run), retries like the sync path, and streams stay
    exact."""
    kw = dict(faults=FaultPlan(seed=1, dispatch_fail_prob=0.25,
                               dispatch_max_failures=2),
              dispatch_retries=8, dispatch_backoff_s=0.0)
    sync = _streams(_run(lm_p, False, _mixed_submits(), **kw))
    eng = _run(lm_p, True, _mixed_submits(), **kw)
    assert _streams(eng) == sync
    assert eng.stats["dispatch_retries"] > 0


def test_async_corrupt_page_replay_exact(lm_p):
    """Corrupt-page recovery is a designated sync point: the pipeline
    drains, the victim replays its delivered prefix, and the final
    streams equal the no-fault sync oracle bit-for-bit."""
    sync = _streams(_run(lm_p, False, _mixed_submits()))
    kw = dict(faults=FaultPlan(seed=5, corrupt_page_prob=0.6),
              dispatch_backoff_s=0.0)
    eng = _run(lm_p, True, _mixed_submits(), **kw)
    assert _streams(eng) == sync
    assert eng.stats["corrupt_page_replays"] > 0


def test_async_snapshot_mid_run_restores_exact(lm_p, tmp_path):
    """Snapshot mid-pipeline drains in-flight blocks, retires streams the
    drain completed, and the restored engine (async again) finishes every
    stream bit-identical to the uninterrupted sync oracle."""
    sync = _streams(_run(lm_p, False, _mixed_submits()))
    path = str(tmp_path / "snap.json")
    eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(42),
                      async_loop=True)
    for kw in _mixed_submits():
        eng.submit(**kw)
    eng.run(max_blocks=3, snapshot_path=path, snapshot_every_blocks=1)
    pre = _streams(eng)
    restored = ServeEngine.from_snapshot(lm_p, path)
    assert restored.async_loop            # the knob rides the snapshot
    restored.run()
    merged = dict(pre)
    merged.update(_streams(restored))
    assert merged == sync
    # restoring into the stepwise oracle drops the pipeline knob instead
    # of refusing (streams are schedule-independent)
    alt = ServeEngine.from_snapshot(lm_p, path, fused=False)
    assert not alt.async_loop


def test_async_cancel_and_deadline_exact(lm_p):
    """Cancel and deadline expiry flush the pipeline first, so the
    partial they cut is bit-identical to the sync partial; a cancel that
    the drain reveals as already-finished reports False and the stream
    completes normally."""
    p = _prompts(3, seed=9)
    submits = [dict(prompt=p[0], max_new_tokens=20),
               dict(prompt=p[1], max_new_tokens=20, arrival_block=1,
                    deadline_ms=1),     # expires on the virtual clock
               dict(prompt=p[2], max_new_tokens=6, arrival_block=1,
                    sampler=Sampler(temperature=1.1))]
    results = {}
    for async_loop in (False, True):
        eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(42),
                          block_time_ms=100.0, async_loop=async_loop)
        rids = [eng.submit(**kw) for kw in submits]
        eng.run(max_blocks=2)
        cancelled = eng.cancel(rids[0])
        eng.run()
        results[async_loop] = (_streams(eng), cancelled)
    assert results[True] == results[False]
    streams, _ = results[True]
    assert any(fr == "expired" for _t, fr in streams.values())


def test_async_router_and_disagg_exact(lm_p):
    """The split threads through Router and DisaggRouter untouched
    (engine_kw forwarding): fleet streams and handoff adoptions equal the
    sync fleet's bit-for-bit."""
    p = _prompts(3, seed=5)
    submits = [dict(prompt=p[0], max_new_tokens=12),
               dict(prompt=p[1], max_new_tokens=8, arrival_block=1,
                    sampler=Sampler(temperature=1.3)),
               dict(prompt=p[2], max_new_tokens=10, arrival_block=1,
                    sampler=Sampler(temperature=0.8))]

    def fleet(cls, async_loop, **kw):
        r = cls(lm_p, 2, rng=jax.random.key(42), block_steps=K,
                async_loop=async_loop, **kw)
        for s in submits:
            r.submit(**s)
        r.run(max_blocks=300)
        return r

    assert (_streams(fleet(Router, True))
            == _streams(fleet(Router, False)))
    da = fleet(DisaggRouter, True, prefill_replicas=1)
    ds = fleet(DisaggRouter, False, prefill_replicas=1)
    assert _streams(da) == _streams(ds)
    assert da.stats["handoffs_adopted"] == len(submits)
    assert da.stats["handoffs_degraded"] == 0


# --------------------------------------------------------------------------
# the contract the loop exists for: zero host blocking between blocks
# --------------------------------------------------------------------------

def test_async_zero_interblock_gap_and_host_ops(lm_c):
    """The measured pipeline contract, per block: with async_loop the
    dispatch of block t+1 precedes the fetch of block t, so every
    tracer-paired fetch-end -> next-dispatch-start gap is EXACTLY zero
    (sync shows real positive gaps on the same workload), while the
    ≤2-host-ops-per-block accounting is unchanged."""
    engines = {}
    for async_loop in (False, True):
        eng = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42),
                          async_loop=async_loop, trace=True)
        for kw in _mixed_submits():
            eng.submit(**kw)
        eng.run()
        engines[async_loop] = eng
    gaps_a, blocked_a = interblock_gaps(engines[True].tracer,
                                        engines[True].lane)
    gaps_s, _ = interblock_gaps(engines[False].tracer,
                                engines[False].lane)
    assert gaps_a and all(g == 0.0 for g in gaps_a)
    assert gaps_s and any(g > 0.0 for g in gaps_s)
    assert blocked_a                       # fetches still happen — later
    for eng in engines.values():
        ops = ((eng.stats["program_calls"] + eng.stats["host_fetches"])
               / eng.stats["decode_blocks"])
        assert ops == 2.0


def test_async_run_trace_reports_gap_surface(lm_c):
    """run_trace carries the pipeline surface: the async_loop flag and
    interblock_gap_ms/fetch_blocked_ms percentiles, with the async gap
    pinned at zero."""
    trace = synthetic_trace(6, 128, prompt_lens=(8,), max_new_tokens=8,
                            mean_interarrival_blocks=0.5, seed=3)
    reports = {}
    for async_loop in (False, True):
        eng = ServeEngine(lm_c, block_steps=K, rng=jax.random.key(1),
                          async_loop=async_loop)
        reports[async_loop] = run_trace(eng, trace)
    assert reports[True]["async_loop"] is True
    assert reports[False]["async_loop"] is False
    assert reports[True]["interblock_gap_ms_mean"] == 0.0
    assert reports[True]["interblock_gap_ms_p99"] == 0.0
    assert reports[False]["interblock_gap_ms_mean"] > 0.0
    assert reports[True]["fetch_blocked_ms_mean"] is not None
    # stream totals unchanged by the pipeline
    for k in ("requests_completed", "total_generated_tokens",
              "host_ops_per_block"):
        assert reports[True][k] == reports[False][k], k


def test_async_requires_fused():
    """The pipeline only exists on the fused path: the stepwise oracle
    cannot double-buffer (it fetches every token), so the combination is
    a loud config error, not a silent fallback."""
    sim = SimCausalLM(max_batch=2, buckets=(8, 16), max_seq_len=64)
    with pytest.raises(ValueError, match="async_loop requires fused"):
        ServeEngine(sim, block_steps=K, fused=False, async_loop=True)


# --------------------------------------------------------------------------
# sim mode models the pipeline (sim-vs-real schedule pins hold)
# --------------------------------------------------------------------------

def test_sim_async_matches_sim_sync_streams():
    """Zero-XLA matrix sweep: a sim engine's async streams equal its sync
    streams over a 20-request arrival trace (the cheap analogue of the
    real-lm matrix above — same scheduler, same deferral machinery)."""
    def mk():
        return SimCausalLM(max_batch=3, buckets=(8, 16), max_seq_len=64,
                           vocab_size=128, page_size=PAGE,
                           page_pool_pages=40)

    trace = synthetic_trace(20, 128, seed=3)
    outs = {}
    for async_loop in (False, True):
        eng = ServeEngine(mk(), block_steps=K, rng=jax.random.key(1),
                          async_loop=async_loop)
        rep = run_trace(eng, trace)
        outs[async_loop] = (_streams(eng), rep["requests_completed"],
                            rep["total_generated_tokens"],
                            rep["host_ops_per_block"])
    assert outs[True] == outs[False]


def test_sim_async_schedule_matches_real_async(lm_p):
    """THE sim honesty pin, extended to the pipeline: the sim engine's
    ASYNC admission/retire schedule (per-request queue/ttft/retire blocks
    — not just streams) equals a real paged engine's async schedule on
    the same trace, because sim mode models in-flight blocks with the
    same done-carry the device would have (``_sim_end_done``)."""
    trace = synthetic_trace(8, 128, prompt_lens=(8,), max_new_tokens=8,
                            mean_interarrival_blocks=0.5, seed=7)
    scheds = {}
    for name, lm in (("real", lm_p),
                     ("sim", SimCausalLM(max_batch=3, buckets=(8, 16),
                                         max_seq_len=64, vocab_size=128,
                                         page_size=PAGE,
                                         page_pool_pages=40))):
        eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(1),
                          async_loop=True)
        run_trace(eng, trace)
        scheds[name] = sorted(
            (c.request_id, c.queue_blocks, c.ttft_blocks, c.decode_blocks,
             len(c.tokens)) for c in eng.completed)
    assert scheds["real"] == scheds["sim"]
