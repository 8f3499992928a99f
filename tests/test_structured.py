"""Structured decoding gates (ISSUE 13 tentpole).

The grammar subsystem's whole value is two theorems, both pinned here:

* constrained output ALWAYS parses — every constrained completion
  fullmatches its regex (Python ``re`` as the independent oracle, the
  token DFA as the self-check) or ``json.loads``-parses, across fused vs
  stepwise engines, paged vs contiguous caches, greedy and sampled rows,
  chunked and one-shot prefill, budget-ended and accept-terminal-ended
  streams, snapshot-resumed streams, and under the seeded ``grammar``
  fault seam;
* unconstrained rows are UNTOUCHED — free-form requests in a mixed pool
  emit streams bit-identical to a pool compiled with no grammar support
  at all (the identity slot's all-ones mask leaves logits bit-for-bit
  alone), and the ≤2-host-ops-per-block contract holds with grammars
  active, counted from tracer spans.

Plus the compiled-program contract (zero recompiles when the grammar mix
changes — tables are inputs), the structured ``grammar_pool_exhausted``
rejection, ``finish_reason="grammar_accept"``, and the Router fleet
registration / drain-pin-migration satellites.

Tier-1 cost discipline: ONE module-scoped grammar CausalLM (+ one paged
twin and one grammarless reference) serve every test; block_steps=4
throughout so each lm compiles a single session program.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.faults import FaultPlan
from neuronx_distributed_tpu.inference.grammar import (
    GrammarCompileError,
    compile_token_dfa,
    default_token_table,
    detokenize,
    json_schema_to_regex,
)
from neuronx_distributed_tpu.inference.router import Router
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
SLOTS, STATES = 3, 48       # identity + 2 resident: 3 grammars MUST churn
TABLE = default_token_table(128)

NUM_RE = "-?[0-9]{1,3}"
AB_RE = "a[ab]*b"           # unbounded: terminates via budget-aware mask
JSON_SCHEMA = {"type": "object", "properties": {
    "a": {"type": "integer"}, "ok": {"type": "boolean"}}}
SPECS = {"gnum": {"regex": NUM_RE}, "gab": {"regex": AB_RE},
         "gjson": {"json_schema": JSON_SCHEMA}}


@pytest.fixture(scope="module")
def base():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return cfg, params


@pytest.fixture(scope="module")
def lm(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, grammar_slots=SLOTS,
                    grammar_states=STATES).compile()


@pytest.fixture(scope="module")
def lm_paged(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=4, grammar_slots=SLOTS,
                    grammar_states=STATES).compile()


@pytest.fixture(scope="module")
def lm_plain(base):
    """The bitwise-identity reference: same weights, NO grammar support —
    its compiled session programs have no ``*gr`` tail at all."""
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3).compile()


def _prompts(n, s=8, seed=5):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


P = _prompts(4)

# the canonical mixed schedule: a free-form greedy and a free-form sampled
# row decode NEXT TO a terminal-bounded grammar, an unbounded grammar
# (sampled — termination must come from the budget-aware mask) and a
# JSON-schema grammar, with a third grammar arriving after a slot freed so
# its load must evict a cold grammar mid-trace (SLOTS = identity + 2)
SUBMITS = [dict(prompt=P[0], max_new_tokens=6),
           dict(prompt=P[1], max_new_tokens=5,
                sampler=Sampler(temperature=0.9), arrival_block=1),
           dict(prompt=P[2], max_new_tokens=6, grammar="gnum",
                arrival_block=2),
           dict(prompt=P[3], max_new_tokens=7, grammar="gab",
                sampler=Sampler(temperature=1.3), arrival_block=3),
           dict(prompt=P[0], max_new_tokens=24, grammar="gjson",
                arrival_block=6)]


def _register(target, specs=SPECS):
    for name, spec in specs.items():
        target.register_grammar(name, **spec)


def _run(lm_, fused, submits=SUBMITS, faults=None, rng_seed=42, **kw):
    eng = ServeEngine(lm_, block_steps=K, fused=fused,
                      rng=jax.random.key(rng_seed), faults=faults, **kw)
    if getattr(lm_, "grammar", False):
        _register(eng)
    rids = [eng.submit(**s) for s in submits]
    comps = {c.request_id: c for c in eng.run()}
    return eng, rids, comps


# --- compiler units -------------------------------------------------------


def test_regex_compiler_matches_python_re():
    """The dialect is a Python-re subset: for every supported feature the
    token DFA's accept decision agrees with ``re.fullmatch`` (single-char
    walks — the independent oracle the parse gate reuses)."""
    cases = [
        ("(ab|cd)+", ["ab", "abcd", "cdab"], ["a", "abc", ""]),
        ("x{2,4}", ["xx", "xxxx"], ["x", "xxxxx"]),
        ("x{2,}", ["xx", "xxxxx"], ["x"]),
        ("[^0-9]{2}", ["ab", "!?"], ["a1", "a"]),
        ("a?b+c", ["bc", "abbc"], ["ac", "ab"]),
        ("\\d+(\\.\\d+)?", ["12", "3.14"], [".5", "1."]),
        ("[a-c]*z", ["z", "abcz"], ["abz2", "d"]),
        ("(get|set)\\(\"[a-z]{1,3}\"\\)", ['get("ab")'], ["get(ab)"]),
    ]
    for pat, goods, bads in cases:
        g = compile_token_dfa(pat, TABLE)

        def walk(text):
            s = 0
            for ch in text:
                s = g.walk(s, TABLE.index(ch))
                if s < 0:
                    return False
            return bool(g.accept[s])

        for t in goods:
            assert walk(t) and re.fullmatch(pat, t), (pat, t)
        for t in bads:
            assert not walk(t) and not re.fullmatch(pat, t), (pat, t)


def test_grammar_compile_errors():
    """Bad patterns and uncompletable grammars reject at COMPILE time —
    never after device work started."""
    for pat in ("[z", "(a", "a{3,1}", "*a", "a|)"):
        with pytest.raises(GrammarCompileError):
            compile_token_dfa(pat, TABLE)
    # empty-only match: a decode stream must emit >= 1 token
    with pytest.raises(GrammarCompileError):
        compile_token_dfa("a{0}", TABLE)
    # satisfiable chars that no token produces -> no token sequence
    with pytest.raises(GrammarCompileError):
        compile_token_dfa("é+", TABLE)


def test_json_schema_lowering_loads():
    """Every schema the subset supports lowers to a regex whose matches
    ``json.loads``-parse; unsupported shapes raise."""
    schema = {"type": "object", "properties": {
        "name": {"type": "string"}, "n": {"type": "number"},
        "tags": {"type": "array", "items": {"type": "integer"},
                 "maxItems": 3},
        "kind": {"enum": ["a", "bc"]}, "none": {"type": "null"}}}
    g = compile_token_dfa(json_schema_to_regex(schema), TABLE)
    # greedy first-allowed walk with a generous budget must parse
    s, out = 0, []
    for k in range(64):
        row = g.allowed_row(s, 64 - k - 1)
        if not row.any():
            break
        v = int(np.argmax(row))
        out.append(v)
        s = g.walk(s, v)
        if g.terminal[s]:
            break
    doc = json.loads(detokenize(out, TABLE))
    assert set(doc) == {"name", "n", "tags", "kind", "none"}
    with pytest.raises(GrammarCompileError):
        json_schema_to_regex({"type": "object", "properties": {
            "x": {"type": "tuple"}}})


# --- the serving oracles --------------------------------------------------


def _assert_parses(comps, rids):
    t_num = detokenize(comps[rids[2]].tokens, TABLE)
    assert re.fullmatch(NUM_RE, t_num), t_num
    t_ab = detokenize(comps[rids[3]].tokens, TABLE)
    assert re.fullmatch(AB_RE, t_ab), t_ab
    t_js = detokenize(comps[rids[4]].tokens, TABLE)
    assert json.loads(t_js) is not None
    return t_num, t_ab, t_js


def test_structured_streams_always_parse_matrix(lm, lm_paged):
    """THE parse oracle: constrained completions out of a mixed pool with
    mid-trace grammar load/evict churn parse in EVERY mode — fused vs
    stepwise × paged vs contiguous, greedy and sampled, accept-terminal
    and budget-ended — and all four engines emit bit-identical streams."""
    results = {}
    engines = {}
    for tag, lm_ in (("contig", lm), ("paged", lm_paged)):
        for fused in (True, False):
            eng, rids, comps = _run(lm_, fused)
            results[(tag, fused)] = {r: comps[r].tokens.tolist()
                                     for r in rids}
            engines[(tag, fused)] = (eng, rids, comps)
    first = results[("contig", True)]
    for key, res in results.items():
        assert res == first, key
    eng, rids, comps = engines[("contig", True)]
    _assert_parses(comps, rids)
    # and the DFA's own verdict agrees on every constrained stream
    pool = eng.session.grammars
    for i, g in ((2, "gnum"), (3, "gab"), (4, "gjson")):
        assert pool.grammar(g).fullmatch_ids(comps[rids[i]].tokens), g
    # finish reasons: terminal-bounded grammars end in grammar_accept; the
    # unbounded sampled gab ends wherever the budget-aware mask parked it
    # (budget in an accept state also parses — asserted above)
    assert comps[rids[2]].finish_reason == "grammar_accept"
    assert comps[rids[4]].finish_reason == "grammar_accept"
    assert comps[rids[3]].finish_reason in ("grammar_accept", "budget")
    assert comps[rids[2]].grammar == "gnum"
    # churn really happened: the third grammar's load evicted a cold one
    for eng_, _r, _c in engines.values():
        assert eng_.session.grammars.stats["evictions"] >= 1
        assert eng_.stats["grammar_rejects"] == 0


def test_mixed_pool_freeform_rows_bit_identical_to_grammarless(
        lm, lm_plain):
    """THE bitwise oracle: free-form rows decoding NEXT TO constrained
    rows emit the exact streams of a pool compiled with no grammar
    support at all (same weights, same request ids — the identity slot's
    all-ones mask leaves their logits untouched bit-for-bit)."""
    for fused in (True, False):
        _, rids, comps = _run(lm, fused)
        eng_p = ServeEngine(lm_plain, block_steps=K, fused=fused,
                            rng=jax.random.key(42))
        free = [SUBMITS[0], SUBMITS[1]]
        rids_p = [eng_p.submit(**{**s, "request_id": rids[i]})
                  for i, s in enumerate(free)]
        comps_p = {c.request_id: c for c in eng_p.run()}
        for i in range(len(free)):
            assert comps[rids[i]].tokens.tolist() == \
                comps_p[rids_p[i]].tokens.tolist(), (fused, i)


def test_zero_recompiles_when_grammar_mix_changes(lm):
    """Compiled-program cache identity: the mask/next tables ride every
    program as an INPUT, so a different grammar mix (different residency,
    different churn) compiles nothing new."""
    _run(lm, True)
    _run(lm, False)
    before = dict(lm.compile_ms)
    alt = [dict(prompt=P[0], max_new_tokens=24, grammar="gjson"),
           dict(prompt=P[1], max_new_tokens=5, grammar="gab",
                arrival_block=1),
           dict(prompt=P[2], max_new_tokens=4, grammar="gnum",
                arrival_block=5)]
    for fused in (True, False):
        eng, _, _ = _run(lm, fused, submits=alt, rng_seed=1)
        assert eng.session.grammars.stats["loads"] >= 2
    assert dict(lm.compile_ms) == before, (
        set(lm.compile_ms) - set(before))


def test_chunked_prefill_under_grammar_matches_one_shot(lm):
    """Chunked admission under a grammar: a 16-token prompt prefilled 4
    tokens per round emits the bit-identical constrained stream of the
    one-shot insert, and it still parses."""
    prompt = _prompts(1, s=16, seed=9)[0]

    def run_one(chunk):
        eng = ServeEngine(lm, block_steps=K, prefill_chunk_tokens=chunk,
                          rng=jax.random.key(3))
        _register(eng)
        rid = eng.submit(prompt, 7, grammar="gab",
                         sampler=Sampler(temperature=1.1))
        comps = {c.request_id: c for c in eng.run()}
        return eng, comps[rid].tokens.tolist()

    eng_c, chunked = run_one(4)
    assert eng_c.stats["chunk_program_calls"] >= 4
    _eng, one_shot = run_one(0)
    assert chunked == one_shot
    assert re.fullmatch(AB_RE, detokenize(chunked, TABLE))


def test_snapshot_mid_constrained_stream_resumes_exact(lm):
    """Crash recovery mid-constrained-stream: the snapshot carries
    (grammar name, DFA state); from_snapshot re-registers the grammars,
    the replay walks the delivered tokens to restore the DFA state, and
    the resumed stream is bit-identical — so it still parses."""
    _, rids_o, comps_o = _run(lm, True)
    oracle = {r: comps_o[r].tokens.tolist() for r in rids_o}
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42))
    _register(eng)
    rids = [eng.submit(**s) for s in SUBMITS]
    eng.run(max_blocks=8)   # gjson (arrival 6, 24 tokens) is mid-stream
    snap = eng.snapshot()
    assert any(r.get("grammar") == "gjson" and r["state"] == "decoding"
               and r.get("grammar_state", 0) > 0
               for r in snap["requests"]), "no mid-stream constrained req"
    eng2 = ServeEngine.from_snapshot(lm, snap, grammars=SPECS)
    done = {c.request_id: c.tokens.tolist() for c in eng.completed}
    for c in eng2.run():
        done.setdefault(c.request_id, c.tokens.tolist())
    assert done == oracle
    assert json.loads(detokenize(done[rids[4]], TABLE)) is not None


def test_grammar_pool_exhausted_structured_reject(lm):
    """Pool full and nothing evictable (both usable slots pinned by live
    constrained streams): the third grammar's admission is shed with
    Rejected(reason='grammar_pool_exhausted') and a retry-after; the same
    request admits cleanly once pins return."""
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(1))
    _register(eng)
    names = ("gnum", "gab", "gjson")
    rids = [eng.submit(P[i], 24, grammar=g) for i, g in enumerate(names)]
    comps = eng.run()
    assert len(comps) == 2
    assert len(eng.rejected) == 1
    rej = eng.rejected[0]
    assert rej.reason == "grammar_pool_exhausted"
    assert rej.retry_after_blocks >= 1
    assert eng.stats["grammar_rejects"] == 1
    victim = next(i for i in range(3) if rids[i] == rej.request_id)
    eng2 = ServeEngine(lm, block_steps=K, rng=jax.random.key(1))
    _register(eng2)
    rid = eng2.submit(P[victim], 24, grammar=names[victim])
    comps2 = {c.request_id: c for c in eng2.run()}
    assert comps2[rid].finish_reason in ("grammar_accept", "budget")


def test_submit_validation(lm):
    """Rejection at submit: unknown grammar, a budget below the grammar's
    shortest accept distance (the stream could NEVER parse), and a
    compile error at register."""
    eng = ServeEngine(lm, block_steps=K)
    _register(eng)
    with pytest.raises(ValueError, match="unknown grammar"):
        eng.submit(P[0], 8, grammar="nope")
    # gjson's minimal document needs far more than 3 tokens
    with pytest.raises(ValueError, match="could\\s+never parse"):
        eng.submit(P[0], 3, grammar="gjson")
    with pytest.raises(GrammarCompileError):
        eng.register_grammar("bad", regex="[z")
    with pytest.raises(ValueError, match="exactly one"):
        eng.register_grammar("both", regex="a", json_schema={})


def test_grammar_fault_seam_chaos_replay_identical(lm):
    """The seeded ``grammar`` seam: injected table-load failures requeue-
    and-retry, corrupted device mask tables are caught by checksum and
    repaired from the registry (the failure that would otherwise emit an
    out-of-grammar token) — streams stay bit-identical to the no-fault
    oracle, and the same plan replayed makes the same decisions."""
    _, rids_o, comps_o = _run(lm, True)
    oracle = {r: comps_o[r].tokens.tolist() for r in rids_o}
    plan = dict(seed=3, grammar_load_fail_prob=0.35,
                grammar_corrupt_prob=0.35)
    runs = []
    for _ in range(2):
        eng, rids, comps = _run(lm, True, faults=FaultPlan(**plan))
        runs.append(({r: comps[r].tokens.tolist() for r in rids},
                     dict(eng._injector.stats),
                     eng.session.grammars.stats["repairs"],
                     int(eng.stats["grammar_load_retries"])))
    assert runs[0] == runs[1], "fault plan must replay identically"
    res, istats, repairs, retries = runs[0]
    assert res == oracle
    assert istats["grammar_load_faults"] + istats["grammar_corruptions"] >= 2
    assert (istats["grammar_corruptions"] == 0 or repairs >= 1)
    assert (istats["grammar_load_faults"] == 0 or retries >= 1)
    # the streams still parse under chaos (same tokens as oracle, but pin
    # the property the seam exists for)
    eng_l, rids_l, comps_l = _run(lm, True, faults=FaultPlan(**plan))
    _assert_parses(comps_l, rids_l)


def test_host_ops_per_block_with_grammars_active(lm):
    """The dispatch contract with structured decoding ON, counted from
    tracer spans (not engine stats): one program call + one fetch per
    K-token block — the mask transition lives inside the scan, the DFA
    mirror is a pure function of the fetched emissions."""
    from tests.helpers import decode_host_ops_per_block, dispatch_counts

    eng, rids, comps = _run(lm, True, trace=True)
    assert decode_host_ops_per_block(eng) == 2.0
    c = dispatch_counts(eng)
    assert c["decode"] == eng.stats["decode_blocks"]
    assert c["fetch"] == eng.stats["decode_blocks"]
    _assert_parses(comps, rids)


def test_router_fleet_registration_and_drain_migrates_grammar_pins(lm):
    """Router satellites: register_grammar is fleet-wide, a drained
    replica's queued constrained work migrates WITH its pin (released at
    the source, re-pinned at the destination), zero tokens are lost, and
    the failed-over stream equals its solo run — still parsing."""
    router = Router(lm, 2, placement="least_loaded", block_steps=K,
                    rng=jax.random.key(1))
    _register(router)
    rA = router.submit(P[0], 12, grammar="gab",
                       sampler=Sampler(temperature=1.2))
    router.step_block()
    src = next(i for i, eng in enumerate(router.engines)
               if any(r is not None for r in eng.slots))
    rB = router.submit(P[1], 6, grammar="gnum",
                       arrival_block=router.blocks + 1)
    router.drain(src)
    comps = {c.request_id: c for c in router.run()}
    assert len(comps[rA].tokens) >= 1 and len(comps[rB].tokens) >= 1
    assert re.fullmatch(AB_RE, detokenize(comps[rA].tokens, TABLE))
    assert re.fullmatch(NUM_RE, detokenize(comps[rB].tokens, TABLE))
    dst = 1 - src
    assert router.engines[dst].session.grammars.is_resident("gnum")
    assert router.engines[src].session.grammars.pinned("gab") == 0
    # rB equals its solo run under the same request id (the per-request
    # rng contract makes constrained streams placement-independent)
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(1))
    _register(eng)
    solo = eng.submit(P[1], 6, grammar="gnum", request_id=rB)
    solo_comps = {c.request_id: c for c in eng.run()}
    assert comps[rB].tokens.tolist() == solo_comps[solo].tokens.tolist()
