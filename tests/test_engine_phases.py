"""The engine names its own round (ISSUE 39).

A traced ``step_block()`` is ONE ``step_block`` span on the ``(lane,
"phases")`` track, tiled by ``admit``, ``observe``, ``launch``, the dispatch
lane's ``fetch`` and ``harvest``; an insert is an ``admission`` span inside
``admit`` that says whom it stalled; the cache's host half of an insert is
``cache_plan`` and ``cache_commit`` on ``("cache", "pool")``. ``queued`` ends
where the slot is claimed, before the insert it waited for. With an
``annotate`` hook every ``Tracer.span()`` also runs inside
``annotate("nxd:" + name)``: the same span on a profiler's clock. Tracing off:
an empty ring, a hook never called, the same tokens.

One tiny paged, fused ``CausalLM`` serves every engine test; the tracer's
own cases need no model.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability import Tracer
from tests import tiny

TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
K = 4
TILES = ("admit", "observe", "launch", "fetch", "harvest")
HOST_ARGS = 3       # host arrays a fused block's call carries: tok, done, rows


class Recorder:
    """A fake ``annotate``: what was entered and left, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        @contextlib.contextmanager
        def entered():
            self.log.append(("enter", name))
            try:
                yield
            finally:
                self.log.append(("leave", name))
        return entered()


@pytest.fixture(scope="module")
def lm():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(16, 32), max_batch=3, page_size=4,
                    prefix_cache=True).compile()


def _drive(lm, prepare=None, **engine_kw):
    """Two requests at once, a third into the running batch (two rows
    decoding), a fourth that waits a round for a slot (one row left decoding):
    inserts with nobody to stall and with rows to stall, and a request that
    queues. ``prepare(engine)`` runs before the first round."""
    engine = ServeEngine(lm, block_steps=K, rng=jax.random.key(0), **engine_kw)
    if prepare is not None:
        prepare(engine)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int32) for n in (9, 12, 20, 7)]
    ids = [engine.submit(p, max_new_tokens=budget, arrival_block=0)
           for p, budget in zip(prompts[:2], (14, 6))]
    engine.step_block()
    ids += [engine.submit(p, max_new_tokens=budget, arrival_block=engine.blocks)
            for p, budget in zip(prompts[2:], (5, 6))]
    while engine.step_block():
        pass
    return engine, ids


@pytest.fixture(scope="module")
def traced(lm):
    hook = Recorder()
    engine, ids = _drive(lm, tracer=Tracer(annotate=hook))
    return engine, ids, hook


def _spans(engine, track, name=None):
    return [e for e in engine.tracer.events()
            if e["ph"] == "X" and e["lane"] == (engine.lane, track)
            and (name is None or e["name"] == name)]


def _inside(e, outer):
    return outer["ts"] <= e["ts"] and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]


def _tiles_of(engine, outer):
    got = [e for e in _spans(engine, "phases") + _spans(engine, "dispatch", "fetch")
           if e["name"] in TILES and _inside(e, outer)]
    return sorted(got, key=lambda e: e["ts"])


def test_every_round_is_one_step_block_span_with_its_outcome(traced):
    engine, ids, _ = traced
    rounds = _spans(engine, "phases", "step_block")
    assert len(rounds) == engine.stats["blocks"] + 1           # the last call found nothing to do
    assert [r["args"]["worked"] for r in rounds] == [True] * (len(rounds) - 1) + [False]
    assert sum(r["args"]["decoded"] for r in rounds) == engine.stats["decode_blocks"] > 0
    # numbered by the virtual block the round started at, like the spans inside it
    assert [r["block"] for r in rounds if r["args"]["worked"]] == list(range(engine.stats["blocks"]))


def test_five_consecutive_phases_tile_every_worked_round(traced):
    engine, _, _ = traced
    worked = [r for r in _spans(engine, "phases", "step_block") if r["args"]["decoded"]]
    assert worked
    for outer in worked:
        tiles = _tiles_of(engine, outer)
        assert [t["name"] for t in tiles] == list(TILES)
        assert all(t["block"] == outer["block"] for t in tiles)
        for a, b in zip(tiles, tiles[1:]):                      # one after the other
            assert a["ts"] + a["dur"] <= b["ts"]
            if b["name"] != "fetch":                            # ... and on the SAME stamp
                assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1e-9)
        assert tiles[0]["ts"] == outer["ts"]                    # admit begins where the round did
        # 99 % of a round; of a toy round (4 ms here, 54-104 ms in the chip's cells) all
        # but the spans' own bookkeeping between one tile's end and the next one's start
        uncovered = outer["dur"] - sum(t["dur"] for t in tiles)
        assert 0 <= uncovered <= max(0.01 * outer["dur"], 2e-3)


def test_a_round_that_decodes_nothing_has_no_launch_fetch_or_harvest(traced):
    engine, _, _ = traced
    last = _spans(engine, "phases", "step_block")[-1]
    assert [t["name"] for t in _tiles_of(engine, last)] == ["admit", "observe"]


def test_launch_contains_the_decode_dispatch_and_says_how_many_rows(traced):
    engine, _, _ = traced
    launches = _spans(engine, "phases", "launch")
    decodes = _spans(engine, "dispatch", "decode")
    assert len(launches) == len(decodes) == engine.stats["decode_blocks"]
    for launch, decode in zip(launches, decodes):
        assert _inside(decode, launch) and 1 <= launch["args"]["active"] <= 3


class NoEagerUpload:
    """Stands in for ``jnp`` / ``jax`` in a module's namespace: the module as
    it is, but the eager uploads raise while the guard is armed."""

    def __init__(self, module, names, guard):
        self._module, self._names, self._guard = module, names, guard

    def __getattr__(self, name):
        if name in self._names and self._guard["armed"]:
            def refused(*a, **kw):
                raise AssertionError(f"eager {name} between observe and the decode dispatch")
            return refused
        return getattr(self._module, name)


@contextlib.contextmanager
def no_eager_uploads():
    """``jnp.asarray``, ``jnp.array`` and ``jax.device_put`` raise inside
    ``inference/engine.py`` and ``inference/causal_lm.py`` from the end of an
    engine's ``observe`` (with rows to decode) to its ``decode`` dispatch.
    Yields ``watch(engine)``, which returns the list of the blocks whose launch
    ran under the guard."""
    from neuronx_distributed_tpu.inference import causal_lm as lm_mod, engine as engine_mod

    guard = {"armed": False}

    def watch(engine):
        guarded = []
        observe, dispatch = engine._observe_block, engine._dispatch

        def observed():
            observe()
            guard["armed"] = bool(engine._active.any())

        def dispatched(kind, fn):
            if kind == "decode" and guard["armed"]:
                guarded.append(engine.blocks)
                guard["armed"] = False
            return dispatch(kind, fn)

        engine._observe_block, engine._dispatch = observed, dispatched
        return guarded

    with pytest.MonkeyPatch.context() as patch:
        for mod in (engine_mod, lm_mod):
            patch.setattr(mod, "jnp", NoEagerUpload(jnp, ("asarray", "array"), guard))
            patch.setattr(mod, "jax", NoEagerUpload(jax, ("device_put",), guard))
        try:
            yield watch
        finally:
            guard["armed"] = False


@pytest.fixture(scope="module")
def guarded(lm):
    with no_eager_uploads() as watch:
        blocks = []
        engine, _ = _drive(lm, prepare=lambda e: blocks.append(watch(e)), trace=True)
    return engine, blocks[0]


@pytest.mark.parametrize("insert_before", [True, False], ids=["after_an_insert", "no_insert"])
def test_a_fused_block_launches_with_no_eager_upload(guarded, traced, insert_before):
    """The rows' mirrors ride the program's own call (ISSUE 47): nothing is
    uploaded by hand between ``observe`` and the ``decode`` dispatch, in a
    round that inserted first and in one that did not."""
    engine, under_guard = guarded
    launches = _spans(engine, "phases", "launch")
    assert under_guard == [span["block"] for span in launches]       # every block, guarded
    inserted = {a["block"] for a in _spans(engine, "phases", "admission")}
    mine = [span for span in launches if (span["block"] in inserted) == insert_before]
    assert mine and all(span["args"]["host_args"] == HOST_ARGS for span in mine)
    plain = traced[0]
    assert ({c.request_id: c.tokens.tolist() for c in engine.completed}
            == {c.request_id: c.tokens.tolist() for c in plain.completed})


def test_block_uploads_adds_up_the_launches_host_args(traced):
    engine, _, _ = traced
    launches = _spans(engine, "phases", "launch")
    assert engine.stats["block_uploads"] == sum(s["args"]["host_args"] for s in launches)
    assert engine.stats["block_uploads"] == HOST_ARGS * engine.stats["decode_blocks"] > 0


@pytest.mark.parametrize("engine_kw", [dict(fused=False), dict(async_loop=True)],
                         ids=["stepwise", "async_loop"])
def test_the_guard_bites_the_loops_that_still_upload_by_hand(lm, engine_kw):
    """The stepwise oracle and the async loop are exempt from the rule, so
    under the guard they are what a launch that uploads looks like."""
    with no_eager_uploads() as watch:
        with pytest.raises(AssertionError, match="eager (asarray|array|device_put) between"):
            _drive(lm, prepare=watch, **engine_kw)


@pytest.mark.parametrize("arg", ["rows", "bucket", "decoding", "rids"])
def test_admission_lies_inside_admit_and_says_whom_it_stalled(traced, arg):
    engine, ids, _ = traced
    admissions = _spans(engine, "phases", "admission")
    admits = _spans(engine, "phases", "admit")
    assert len(admissions) == engine.stats["inserts"] == 3
    for a in admissions:
        assert sum(_inside(a, outer) and outer["block"] == a["block"] for outer in admits) == 1
    want = {"rows": [2, 1, 1], "bucket": [16, 32, 16], "decoding": [0, 2, 1],
            "rids": [ids[:2], ids[2:3], ids[3:]]}
    assert [a["args"][arg] for a in admissions] == want[arg]


def test_admission_holds_its_insert_and_the_fetch_of_its_first_tokens(traced):
    """With rows decoding the fetch lies inside the span (they stand still for
    it); with none it follows the span, still inside the round's ``admit``
    (ISSUE 55: nobody waited, so the host did not either)."""
    engine, _, _ = traced
    admits = _spans(engine, "phases", "admit")
    stalled = []
    for admission, insert, fetch in zip(_spans(engine, "phases", "admission"),
                                        _spans(engine, "dispatch", "insert"),
                                        _spans(engine, "dispatch", "insert_fetch")):
        assert _inside(insert, admission) and insert["ts"] + insert["dur"] <= fetch["ts"]
        stalled.append(admission["args"]["decoding"] > 0)
        assert _inside(fetch, admission) == stalled[-1]
        assert sum(_inside(fetch, outer) for outer in admits) == 1
    assert stalled == [False, True, True]


@pytest.mark.parametrize("name", ["cache_plan", "cache_commit"])
def test_cache_spans_lie_inside_the_insert_on_the_pool_lane(traced, name):
    engine, _, _ = traced
    spans = [e for e in engine.tracer.events(name) if e["lane"] == ("cache", "pool")]
    inserts = _spans(engine, "dispatch", "insert")
    assert len(spans) == len(inserts) == 3
    for span, insert, rows in zip(spans, inserts, (2, 1, 1)):
        assert span["ph"] == "X" and _inside(span, insert)
        assert span["args"] == {"rows": rows} and span["block"] == insert["block"]
    plans = [e for e in engine.tracer.events("cache_plan")]
    commits = [e for e in engine.tracer.events("cache_commit")]
    assert all(p["ts"] + p["dur"] <= c["ts"] for p, c in zip(plans, commits))


def test_queued_ends_where_the_slot_is_claimed_before_the_insert(traced):
    engine, ids, _ = traced
    inserts = _spans(engine, "dispatch", "insert")
    admissions = _spans(engine, "phases", "admission")
    by_rid = engine.tracer.by_request()
    for admission, insert in zip(admissions, inserts):
        for rid in admission["args"]["rids"]:
            evs = {e["name"]: e for e in by_rid[rid] if e["name"] in ("queued", "admit", "first_token")}
            queued, admit, first = evs["queued"], evs["admit"], evs["first_token"]
            end = queued["ts"] + queued["dur"]
            assert end <= insert["ts"]                           # not after the fetch, as it was
            assert admit["ts"] == pytest.approx(end, abs=1e-9)   # one stamp: the claim
            assert admit["ts"] <= admission["ts"] <= insert["ts"]    # taken as the group was
            assert admission["ts"] - admit["ts"] < 1e-3              # claimed, then the span opened
            assert first["ts"] >= insert["ts"] + insert["dur"]   # first_token keeps its stamp
    # the request that found no slot waited out blocks; the others none
    waits = {rid: next(e for e in by_rid[rid] if e["name"] == "queued") for rid in ids}
    assert waits[ids[3]]["args"]["queue_blocks"] > 0 == waits[ids[0]]["args"]["queue_blocks"]
    assert waits[ids[3]]["dur"] > waits[ids[2]]["dur"]


def test_every_span_runs_inside_its_annotation_in_order(traced):
    """What the engine opened with ``span()`` is on the hook too, properly
    nested (an ``admission`` inside ``admit`` inside ``step_block``), and what
    it wrote after the fact with ``complete()`` is not."""
    engine, _, hook = traced
    depth, opened = [], []
    for what, name in hook.log:
        assert name.startswith("nxd:")
        if what == "enter":
            depth.append(name)
            opened.append(name)
        else:
            assert depth.pop() == name
    assert depth == []
    by_span = sorted(e["name"] for e in engine.tracer.events() if e["ph"] == "X"
                     and e["name"] in ("step_block", "admit", "admission", "observe", "launch",
                                       "harvest", "cache_plan", "cache_commit"))
    assert sorted(n[4:] for n in opened) == by_span
    assert not {"nxd:fetch", "nxd:insert_fetch", "nxd:decode_block", "nxd:insert"} & set(opened)
    first = opened[:5]
    assert first == ["nxd:step_block", "nxd:admit", "nxd:admission", "nxd:cache_plan",
                     "nxd:cache_commit"]


def test_span_leaves_its_annotation_when_the_body_raises():
    hook = Recorder()
    tracer = Tracer(annotate=hook)
    with pytest.raises(KeyError):
        with tracer.span("launch", ("engine", "phases"), block=3, args={"active": 2}):
            raise KeyError("boom")
    assert hook.log == [("enter", "nxd:launch"), ("leave", "nxd:launch")]
    (ev,) = tracer.events("launch")
    assert ev["args"] == {"active": 2, "error": "KeyError"} and ev["block"] == 3


def test_span_reads_its_args_when_the_body_has_ended():
    tracer = Tracer()
    args = {}
    with tracer.span("step_block", ("engine", "phases"), args=args):
        args["worked"] = True
    assert tracer.events("step_block")[0]["args"] == {"worked": True}


def test_span_binds_its_stamps_and_may_begin_where_the_one_before_ended():
    tracer = Tracer()
    with tracer.span("admit", ("engine", "phases")) as first:
        assert first.end is None
    with tracer.span("observe", ("engine", "phases"), start=first.end) as second:
        pass
    admit, observe = tracer.events()
    assert (admit["ts"], admit["ts"] + admit["dur"]) == pytest.approx((first.start, first.end))
    assert observe["ts"] == first.end == second.start and second.end >= second.start
    with Tracer(enabled=False).span("admit", ("engine", "phases"), start=1.0) as nothing:
        assert nothing is None


def test_a_disabled_tracer_never_calls_its_hook_and_spans_cost_one_shared_object():
    hook = Recorder()
    tracer = Tracer(enabled=False, annotate=hook)
    a = tracer.span("admit", ("engine", "phases"))
    b = tracer.span("observe", ("engine", "phases"))
    with a, b:
        pass
    assert a is b and hook.log == [] and tracer.events() == []


def test_the_engines_own_tracer_mirrors_into_the_profiler_and_a_given_one_says_for_itself(lm):
    assert ServeEngine(lm, block_steps=K, trace=True).tracer.annotate is jax.profiler.TraceAnnotation
    assert ServeEngine(lm, block_steps=K).tracer.annotate is None
    given = Tracer()
    assert ServeEngine(lm, block_steps=K, tracer=given).tracer.annotate is None


def test_the_tracer_module_imports_the_standard_library_only():
    import ast
    import sys
    from pathlib import Path

    from neuronx_distributed_tpu.observability import tracer

    tree = ast.parse(Path(tracer.__file__).read_text())
    roots = {(n.names[0].name if isinstance(n, ast.Import) else n.module).split(".")[0]
             for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))}
    assert roots <= set(sys.stdlib_module_names) | {"__future__"}, roots


def test_tracing_off_records_nothing_and_serves_the_same_tokens(lm, traced):
    on, ids, _ = traced
    off, ids_off = _drive(lm)            # trace=False: the default
    assert off.tracer.events() == [] and off.tracer.dropped == 0 and not off.tracer.enabled
    assert ids_off == ids

    def tokens(engine):
        return {c.request_id: c.tokens.tolist() for c in engine.completed}

    assert tokens(off) == tokens(on) and len(tokens(on)) == 4
    # a disabled tracer with a hook: the engine never calls it
    hook = Recorder()
    muted, _ = _drive(lm, tracer=Tracer(enabled=False, annotate=hook))
    assert hook.log == [] and muted.tracer.events() == [] and tokens(muted) == tokens(on)
    # and under the profiler's own annotation, end to end
    real, _ = _drive(lm, trace=True)
    assert tokens(real) == tokens(on)
    assert len(_spans(real, "phases", "step_block")) == len(_spans(on, "phases", "step_block"))


def test_stepwise_oracle_keeps_its_dispatches_and_fetches_inside_launch(lm):
    engine = ServeEngine(lm, block_steps=K, rng=jax.random.key(0), trace=True, fused=False)
    engine.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=5, arrival_block=0)
    while engine.step_block():
        pass
    launches = _spans(engine, "phases", "launch")
    fetches = _spans(engine, "dispatch", "fetch")
    assert len(fetches) == K * len(launches) > 0
    assert all(any(_inside(f, outer) for outer in launches) for f in fetches)
    assert len(_spans(engine, "phases", "harvest")) == len(launches)


def test_async_loop_has_the_round_span_and_no_phases_yet(lm):
    engine, _ = _drive(lm, trace=True, async_loop=True)
    names = {e["name"] for e in _spans(engine, "phases")}
    assert names == {"step_block", "admission"}
    rounds = _spans(engine, "phases", "step_block")
    assert sum(r["args"]["decoded"] for r in rounds) == engine.stats["decode_blocks"] > 0
