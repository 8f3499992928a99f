"""Continuous-batching engine equivalence suite (ISSUE 2 tentpole gates).

The serving engine's whole value is that fusing the multi-slot decode loop
changes NOTHING about the tokens: every test here pins bit-identity between
(a) the fused K-step session program, (b) the stepwise per-token session
oracle (same scheduler, same rng fold-in), and (c) plain ``generate`` of the
same prompt — under staggered insert/retire, slot reuse after EOS, mixed
per-request samplers, and right-sized inserts. Plus the dispatch contract:
<= 2 host ops per K-token block, proven by counting compiled-program
invocations, not by trusting the engine's own stats.

Tier-1 cost discipline: ONE module-scoped CausalLM serves every non-slow
test (block_steps=4 throughout, so the whole file compiles a single session
program; program caches live on the lm and are shared across engines).
"""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4  # the one fused block size tier-1 compiles


def _make_lm(max_batch=3, buckets=(8, 16), seed=0, **over):
    cfg = LlamaConfig(**{**TINY, **over})
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=seed)
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=buckets,
                    max_batch=max_batch).compile()


@pytest.fixture(scope="module")
def lm():
    return _make_lm()


def _prompts(n, s=8, seed=2):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _run_engine(lm_, fused, submits, rng_seed=42, trace=False, **eng_kw):
    eng = ServeEngine(lm_, block_steps=K, fused=fused, trace=trace,
                      rng=jax.random.key(rng_seed), **eng_kw)
    ids = [eng.submit(**kw) for kw in submits]
    comps = {c.request_id: c for c in eng.run()}
    return eng, ids, comps


def test_session_fused_matches_stepwise_and_generate_greedy(lm):
    """Greedy requests, staggered arrivals: fused == stepwise == solo
    generate, token for token."""
    p = _prompts(3)
    submits = [dict(prompt=p[0], max_new_tokens=9),
               dict(prompt=p[1], max_new_tokens=6, arrival_block=1),
               dict(prompt=p[2], max_new_tokens=7, arrival_block=2)]
    results = {}
    for fused in (True, False):
        _, ids, comps = _run_engine(lm, fused, submits)
        results[fused] = {r: comps[r].tokens.tolist() for r in ids}
    assert results[True] == results[False]
    for i, sub in enumerate(submits):
        golden = lm.generate(p[i : i + 1], max_new_tokens=sub["max_new_tokens"])
        assert results[True][i] == golden.tokens[0].tolist(), f"request {i}"


def test_session_fused_matches_stepwise_sampled_mixed(lm):
    """Per-request samplers (greedy next to two different temperatures in
    ONE slot pool): fused == stepwise bit-identical, and the greedy row is
    unperturbed by its sampled neighbours (== solo generate)."""
    p = _prompts(3, seed=5)
    submits = [dict(prompt=p[0], max_new_tokens=9),
               dict(prompt=p[1], max_new_tokens=7,
                    sampler=Sampler(temperature=0.8), arrival_block=1),
               dict(prompt=p[2], max_new_tokens=5,
                    sampler=Sampler(temperature=1.3), arrival_block=2)]
    results = {}
    for fused in (True, False):
        _, ids, comps = _run_engine(lm, fused, submits)
        results[fused] = {r: comps[r].tokens.tolist() for r in ids}
    assert results[True] == results[False]
    golden = lm.generate(p[0:1], max_new_tokens=9)
    assert results[True][0] == golden.tokens[0].tolist()
    # sampled rows actually sampled (not accidentally greedy): lengths filled
    assert len(results[True][1]) == 7 and len(results[True][2]) == 5


def test_session_eos_retires_and_slot_is_reused(lm):
    """Retire-on-EOS mid-block, slot reuse by a queued request, and the
    reused slot's stream equals ITS solo generate — the continuous-batching
    contract under churn (4 requests through 3 slots)."""
    p = _prompts(4, seed=7)
    g0 = lm.generate(p[0:1], max_new_tokens=9)
    eos = int(g0.tokens[0, 3])  # row 0 stops after 4 tokens
    submits = [dict(prompt=p[0], max_new_tokens=9, eos_token_id=eos),
               dict(prompt=p[1], max_new_tokens=8),
               dict(prompt=p[2], max_new_tokens=6),
               dict(prompt=p[3], max_new_tokens=6, arrival_block=1)]
    for fused in (True, False):
        eng, ids, comps = _run_engine(lm, fused, submits)
        ge = lm.generate(p[0:1], max_new_tokens=9, eos_token_id=eos)
        assert comps[ids[0]].tokens.tolist() == \
            ge.tokens[0][: int(ge.lengths[0])].tolist()
        assert comps[ids[0]].tokens[-1] == eos
        g3 = lm.generate(p[3:4], max_new_tokens=6)
        assert comps[ids[3]].tokens.tolist() == g3.tokens[0].tolist(), fused
        # churn happened: more requests than slots
        assert eng.stats["inserted_requests"] == 4 > lm.max_batch


def test_completion_finish_reason_pinned(lm):
    """ISSUE 13 satellite: ``Completion.finish_reason`` names why a stream
    ended — callers previously inferred it by diffing fields. Pins "eos",
    "budget", "expired" and "cancelled" on one engine (the
    "grammar_accept" value is pinned in tests/test_structured.py), and
    that fused and stepwise agree on the reason."""
    p = _prompts(4, seed=11)
    g0 = lm.generate(p[0:1], max_new_tokens=9)
    eos = int(g0.tokens[0, 3])
    submits = [dict(prompt=p[0], max_new_tokens=9, eos_token_id=eos),
               dict(prompt=p[1], max_new_tokens=4),
               dict(prompt=p[2], max_new_tokens=40, deadline_ms=6.0)]
    reasons = {}
    for fused in (True, False):
        eng = ServeEngine(lm, block_steps=K, fused=fused,
                          rng=jax.random.key(42))
        ids = [eng.submit(**kw) for kw in submits]
        eng.run(max_blocks=1)
        comps = {c.request_id: c for c in eng.run()}
        reasons[fused] = {r: comps[r].finish_reason for r in ids}
        assert comps[ids[0]].finish_reason == "eos"
        assert comps[ids[1]].finish_reason == "budget"
        assert comps[ids[2]].finish_reason == "expired"
        assert comps[ids[2]].expired
    assert reasons[True] == reasons[False]
    # cancelled: a fresh decoding stream cancelled mid-flight
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42))
    rid = eng.submit(p[3], 30)
    eng.run(max_blocks=1)
    assert eng.cancel(rid)
    comp = next(c for c in eng.completed if c.request_id == rid)
    assert comp.finish_reason == "cancelled" and comp.cancelled


def test_session_fused_dispatch_count(lm):
    """The dispatch contract, counted three independent ways ON THE SAME
    RUN — tracer dispatch spans (the observability surface), a monkeypatch
    wrapper around the compiled program (the tracer-independent
    cross-check), and the engine's own stats — all agreeing at ONE program
    invocation + ONE fetch per K-token block. Runs with tracing ENABLED,
    which is itself the tentpole's proof that instrumentation does not add
    host ops."""
    from tests.helpers import (
        count_factory_calls, decode_host_ops_per_block, dispatch_counts,
    )

    p = _prompts(2, seed=9)
    with count_factory_calls(lm, "compile_session_decode_fused") as calls:
        eng, ids, comps = _run_engine(
            lm, True, [dict(prompt=p[0], max_new_tokens=10),
                       dict(prompt=p[1], max_new_tokens=7, arrival_block=1)],
            trace=True)
    assert calls.n == eng.stats["decode_blocks"] >= 2
    assert eng.stats["program_calls"] == eng.stats["host_fetches"] == calls.n
    # tracer-counted: decode dispatches == monkeypatch-counted program
    # invocations, and decode + fetch == 2 host ops per block exactly
    assert dispatch_counts(eng, "decode") == calls.n
    assert decode_host_ops_per_block(eng) == 2.0
    rep_ops = (eng.stats["program_calls"] + eng.stats["host_fetches"]) \
        / eng.stats["decode_blocks"]
    assert rep_ops == 2.0
    assert eng.stats["chunk_program_calls"] == 0   # no chunking requested
    # and the counted path produced the uncounted path's tokens
    g0 = lm.generate(p[0:1], max_new_tokens=10)
    assert comps[ids[0]].tokens.tolist() == g0.tokens[0].tolist()


def test_right_sized_insert_touches_only_inserted_rows(lm):
    """The scatter-insert claim, checked on the cache itself: inserting into
    slot 1 leaves every OTHER slot's cache rows bit-identical (the full-width
    ``where`` merge used to rewrite every byte; per-row dynamic updates must
    not perturb neighbours), and per-width prefill programs are cached."""
    p = _prompts(3, seed=11)
    session = lm.start_session()
    lm.insert(session, [0], p[0:1])
    lm.step(session, np.zeros((3,), np.int32))
    before = jax.tree.map(np.asarray, session.cache)
    lm.insert(session, [1], p[1:2])
    after = jax.tree.map(np.asarray, session.cache)

    def check(path, a, b):
        np.testing.assert_array_equal(
            np.delete(a, 1, axis=1), np.delete(b, 1, axis=1),
            err_msg=str(path))

    jax.tree_util.tree_map_with_path(check, before, after)
    # right-sized programs keyed by (rows, bucket): the 1-row inserts above
    # must NOT have compiled a max_batch-wide prefill
    assert (1, 8) in lm._slab_insert
    # a 2-row insert batches through its own width
    lm.retire(session, [0, 1])
    lm.insert(session, [0, 2], p[0:2])
    assert (2, 8) in lm._slab_insert


def test_bucketed_admission_batches_one_insert(lm):
    """Queued same-bucket requests admitted together ride ONE right-sized
    insert (bucketed prefill batching)."""
    p = _prompts(3, seed=13)
    eng = ServeEngine(lm, block_steps=K)
    for i in range(3):
        eng.submit(p[i], 5)
    eng.run()
    assert eng.stats["inserts"] == 1 and eng.stats["inserted_requests"] == 3


# what a scheduler may hold in the six rows a fused block takes as ONE host
# matrix: a per-request EOS beside none (-1), temperatures that are zero, no
# small integer and tiny, counters at both ends of int32
BLOCK_ROWS = dict(
    counts=np.asarray([0, 3, 1000, 2 ** 31 - 1], np.int32),
    lengths=np.asarray([1, 63, 4096, 0], np.int32),
    active=np.asarray([True, False, True, True]),
    eos_ids=np.asarray([-1, 2, 31999, -1], np.int32),
    temperature=np.asarray([0.0, 0.7, 1e-3, 2.5], np.float32),
    greedy=np.asarray([True, False, False, True]))


@pytest.mark.parametrize("row", CausalLM.BLOCK_ROWS)
def test_block_rows_reach_the_program_in_their_dtype_bit_for_bit(row):
    """``CausalLM.block_rows`` on the host, then the program's own unpacking:
    every row comes out in the dtype and with the bits it went up with before
    it shared a matrix (ISSUE 47). Into a kept buffer or a new one alike."""
    assert tuple(BLOCK_ROWS) == CausalLM.BLOCK_ROWS
    packed = CausalLM.block_rows(**BLOCK_ROWS)
    assert packed.dtype == np.int32 and packed.shape == (6, 4)
    kept = np.full((6, 4), 77, np.int32)
    assert CausalLM.block_rows(**BLOCK_ROWS, out=kept) is kept
    assert kept.tobytes() == packed.tobytes()
    for value in BLOCK_ROWS.values():
        assert not np.shares_memory(packed, value)       # a copy: the async loop counts on it
    got = dict(zip(CausalLM.BLOCK_ROWS, jax.jit(CausalLM.unpack_block_rows)(packed)))[row]
    want = BLOCK_ROWS[row]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.asarray(got).tobytes() == want.tobytes()


def test_session_fused_overflow_guard_freezes_not_wraps(lm):
    """Device-side overflow guard: a slot driven to the cache edge inside a
    block freezes (done latch + pad emissions) instead of wrapping writes —
    while a slot with room keeps decoding."""
    max_len = lm.config.max_seq_len  # 64
    fused = lm.compile_session_decode_fused(K)
    session = lm.start_session()
    p = _prompts(3, seed=15)
    lm.insert(session, [0, 1, 2], p)
    # slot 0 reports 2 tokens of room; slot 1 has plenty; slot 2 inactive
    lengths = np.asarray([max_len - 2, 8, 8], np.int32)
    toks, cache, tok, out_len, done, walked = fused(
        lm.params, session.cache, jnp.zeros((3, 1), jnp.int32),
        jax.random.split(jax.random.key(0), 3), jnp.zeros((3,), bool),
        lm.block_rows(np.ones((3,), np.int32), lengths, [True, True, False],
                      np.full((3,), -1), np.zeros((3,)), np.ones((3,), bool)))
    toks, done = np.asarray(toks), np.asarray(done)
    # how far the block read, in how many steps, over how many rows: one chunk (64
    # slots) of every row at each of the K steps, two of the three rows being live
    assert np.asarray(walked).tolist() == [K * max_len, K, K * max_len * 3]
    assert done[0] and not done[1]
    assert (toks[1:, 0] == 0).all(), "frozen slot must emit pad"
    assert (toks[:, 1] != 0).all(), "healthy slot keeps emitting"
    assert (toks[:, 2] == 0).all(), "inactive slot emits pad"


def test_prompt_exactly_at_bucket_boundary(lm):
    """Edge the PR 2 suite skipped: prompts whose length EQUALS a prefill
    bucket (no pad tail at all) ride the engine next to an off-boundary
    prompt, and both streams equal their solo generates — the boundary
    must select the exact-fit bucket, not overflow to the next one."""
    p8 = _prompts(1, s=8, seed=19)       # == bucket 8
    p16 = _prompts(1, s=16, seed=21)     # == bucket 16 (the largest)
    p5 = _prompts(1, s=5, seed=23)[:, :5]
    submits = [dict(prompt=p8[0], max_new_tokens=6),
               dict(prompt=p16[0], max_new_tokens=5, arrival_block=1),
               dict(prompt=p5[0], max_new_tokens=6, arrival_block=1)]
    _, ids, comps = _run_engine(lm, True, submits)
    for i, (prompt, n) in enumerate(((p8, 6), (p16, 5), (p5, 6))):
        g = lm.generate(prompt, max_new_tokens=n)
        assert comps[ids[i]].tokens.tolist() == g.tokens[0].tolist(), i


def test_engine_submit_validation(lm):
    eng = ServeEngine(lm, block_steps=K, top_k=None, top_p=None)
    p = _prompts(1)[0]
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(p, 0)
    with pytest.raises(ValueError, match="cache room"):
        eng.submit(p, 1000)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(p, 4, sampler=Sampler(temperature=1.0, top_k=5))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32), 4)


def test_arrival_trace_report_contract(lm):
    """run_trace over a synthetic arrival trace: every request completes,
    budgets respected, and the report's host-op accounting reflects the
    fused contract."""
    trace = synthetic_trace(5, 128, prompt_lens=(6, 8), max_new_tokens=6,
                            mean_interarrival_blocks=0.7, seed=3)
    eng = ServeEngine(lm, block_steps=K)
    report = run_trace(eng, trace)
    assert report["requests_completed"] == 5
    assert report["total_generated_tokens"] == 5 * 6
    assert report["host_ops_per_block"] == 2.0
    assert report["inserted_requests"] == 5
    assert report["tokens_per_sec"] is not None and report["tokens_per_sec"] > 0
    # latency surface (ISSUE 4 satellite): per-request TTFT + max ITL gap
    assert len(report["per_request"]) == 5
    for pr in report["per_request"]:
        assert pr["ttft_blocks"] >= 0 and pr["max_itl_gap_ms"] >= 0.0
    assert report["itl_p50_ms"] is not None
    assert report["itl_p99_ms"] >= report["itl_p50_ms"]
    assert report["prefill_chunk_tokens"] == 0
    assert report["chunk_program_calls"] == 0


def test_generate_fused_tail_uses_fused_program(lm):
    """ISSUE 2 satellite: a tail shorter than fused_chunk must run as a
    cached tail-sized fused program, not fall back to per-token step decode
    — counted on the step-decode program itself (only a 1-token tail may
    use it)."""
    from tests.helpers import count_calls

    ids = _prompts(2, seed=17)
    ref = lm.generate(ids, max_new_tokens=10)
    with count_calls(lm, "_decode") as step_calls:
        # 10 tokens, chunk 4: prefill token + fused(4) + fused(4) + 1-token
        # tail -> exactly ONE step call
        got = lm.generate(ids, max_new_tokens=10, fused_chunk=K)
        assert step_calls.n == 1
        step_calls.n = 0
        # 8 tokens, chunk 4: prefill token + fused(4) + fused TAIL of 3 ->
        # ZERO step calls (pre-PR the 3-token tail silently step-decoded)
        got8 = lm.generate(ids, max_new_tokens=8, fused_chunk=K)
        assert step_calls.n == 0
    np.testing.assert_array_equal(got.tokens, ref.tokens)
    np.testing.assert_array_equal(got8.tokens, ref.tokens[:, :8])
    # the tail program is cached per size
    assert any(k[0] == 3 for k in lm._decode_fused)


@pytest.mark.slow  # many-request trace at a larger tiny config: throughput
# shape ride-along, not a tier-1 gate
def test_arrival_trace_throughput_fused_beats_stepwise():
    """The point of the whole exercise, at test scale: the fused engine
    completes the same trace with ~K-fold fewer host ops than the stepwise
    oracle and no slower wall clock (CPU timing is noisy — only the op
    accounting is asserted hard)."""
    lm_ = _make_lm(max_batch=4, buckets=(16,), max_seq_len=128)
    trace = synthetic_trace(12, 128, prompt_lens=(8, 12, 16),
                            max_new_tokens=24, mean_interarrival_blocks=0.4,
                            seed=5)
    reports = {}
    for fused in (True, False):
        eng = ServeEngine(lm_, block_steps=8, fused=fused)
        reports[fused] = run_trace(eng, trace)
    assert reports[True]["requests_completed"] == \
        reports[False]["requests_completed"] == 12
    assert reports[True]["host_ops_per_block"] == 2.0
    assert reports[False]["host_ops_per_block"] == 16.0
    assert reports[True]["program_calls"] * 8 == reports[False]["program_calls"]


def test_replay_driver_sits_above_engine_router_and_disagg():
    """The load generator and the reports are one module ABOVE the serving
    stack: loading the engine, the router, the disaggregated fleet or the
    package does not load ``inference.replay``; asking the package for one
    of the replay names does."""
    code = (
        "import sys\n"
        "import neuronx_distributed_tpu.inference as inf\n"
        "import neuronx_distributed_tpu.inference.engine\n"
        "import neuronx_distributed_tpu.inference.router\n"
        "import neuronx_distributed_tpu.inference.disagg\n"
        "replay = 'neuronx_distributed_tpu.inference.replay'\n"
        "assert replay not in sys.modules\n"
        "from neuronx_distributed_tpu.inference import (run_trace, synthetic_trace,\n"
        "    synthetic_trace_stream, run_router_trace, run_disagg_trace)\n"
        "assert replay in sys.modules\n"
        "assert {f.__module__ for f in (run_trace, synthetic_trace, synthetic_trace_stream,\n"
        "    run_router_trace, run_disagg_trace)} == {replay}\n"
        "assert not hasattr(inf, 'no_such_name')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=300, cwd=Path(__file__).resolve().parent.parent)
    assert proc.returncode == 0, proc.stderr[-2000:]
