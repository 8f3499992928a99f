"""The process's compile log (``utils/compile_cache.py``, ISSUE 54), on the CPU.

A named program's owner stamps the parts of its own compile with the clock
(traced and lowered, then compiled by the backend or loaded from the persistent
cache); a pair of ``jax.monitoring`` listeners tells hit from miss and puts
every compilation no row owns into ``unnamed``. Here: the parts tile their
wall, hit and miss follow a cache directory in ``tmp_path``, whose event is
whose, what ``CausalLM``, ``initialize_parallel_model`` and ``ServeEngine`` put
into it and read of it, and that one pair of listeners serves the process. The
CPU keeps tiny programs only with both thresholds at 0, as the benchmark sets
them. The log is the process's: tests on it take differences, tests on
ownership use a log of their own.
"""

import itertools
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax._src import monitoring
from jax.experimental.compilation_cache import compilation_cache
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
from neuronx_distributed_tpu.inference.engine import _STAT_KEYS, _startup_stats
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability import parse_prometheus
from neuronx_distributed_tpu.trainer import initialize_parallel_model, neuronx_distributed_config
from neuronx_distributed_tpu.utils import compile_cache
from neuronx_distributed_tpu.utils.compile_cache import PARTS, CompileLog, compile_log

ROOT = Path(__file__).resolve().parent.parent
TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
# one a ``setup.*`` metric of the benchmark (``engine.py::_startup_stats``)
STARTUP_KEYS = ["startup_trace_lower_ms", "startup_xla_compile_ms", "startup_cache_load_ms",
                "startup_format_ms", "startup_cache_misses", "startup_unnamed_ms",
                "startup_init_ms"]


def _params(seed=0):
    cfg = LlamaConfig(**TINY)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    return cfg, jax.device_put(params, SingleDeviceSharding(jax.devices()[0]))


def _lm(**kw):
    cfg, params = _params()
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16), max_batch=2, **kw)


def _sum(rows, part):
    return sum(r.get(part, 0.0) for r in rows)


@pytest.fixture
def own_log():
    """A log of the test's own, its listeners taken off JAX again."""
    log = CompileLog()
    yield log
    if log._listening:
        monitoring.unregister_event_listener(log._event)
        monitoring.unregister_event_duration_listener(log._duration)


@pytest.fixture
def cache_dir(tmp_path):
    """JAX's persistent cache in ``tmp_path``, every program kept however
    quick; the process's settings put back."""
    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, n) for n in names]
    for name, value in zip(names, (str(tmp_path), 0.0, 0)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield tmp_path
    for name, value in zip(names, was):
        jax.config.update(name, value)
    compilation_cache.reset_cache()


# ------------------------------------------------------------ the parts tile

def test_the_two_parts_tile_the_calls_wall_on_a_made_clock(own_log, monkeypatch):
    """A clock that ticks a second a reading: the row opens at 1, ``staged``
    reads 2, 3, 4, the row closes at 5. The first second is ``trace_lower_ms``,
    the second the backend's, end to end, inside a wall of four."""
    ticks = itertools.count(1)
    monkeypatch.setattr(compile_cache, "time",
                        types.SimpleNamespace(perf_counter=lambda: float(next(ticks))))
    with own_log.program("made") as row:
        compiled = own_log.staged(jax.jit(lambda x: jnp.tanh(x) * 2), jnp.ones((3, 5)))
    assert row["trace_lower_ms"] == 1000.0
    assert row["xla_compile_ms"] + row["cache_load_ms"] == 1000.0
    assert row["wall_ms"] == 4000.0 and own_log.rows == [row]
    np.testing.assert_allclose(compiled(jnp.ones((3, 5))), np.tanh(1.0) * 2, rtol=1e-6)


def test_staged_is_lower_then_compile_and_needs_no_row(own_log):
    """With no row open nothing is stamped and the program is the same."""
    fn, x = jax.jit(lambda x: x @ x.T + 1), jnp.ones((4, 6))
    assert own_log.staged(fn, x).as_text() == fn.lower(x).compile().as_text()
    assert own_log.rows == []


def test_every_programs_parts_stay_inside_its_wall():
    lm = _lm()
    lm.compile()
    session = lm.start_session()
    lm.insert(session, np.arange(2), np.ones((2, 8), np.int32))
    rows = lm.compile_rows()
    assert [r["name"] for r in rows] == list(lm.compile_ms) and len(rows) >= 4
    for row in rows:
        parts = [row.get(p, 0.0) for p in PARTS]
        assert all(p >= 0 for p in parts) and row["trace_lower_ms"] > 0
        assert sum(parts) <= row["wall_ms"] + 0.05            # rounded to 0.01 each
        # the wall timer ``compile_ms`` is the row's, to the stamp inside it
        assert lm.compile_ms[row["name"]] <= row["wall_ms"] + 0.01
        assert sum(parts) <= lm.compile_ms[row["name"]] + 0.05
    # the rows the lm hands back are the process log's own, in its order
    mine = [r for r in compile_log.rows if r in lm._compile_rows]
    assert mine == lm._compile_rows


def test_compile_ms_has_the_keys_it_always_had():
    """The same calls, the same signatures: ``setup.programs`` counts these and
    ``setup.compile_s`` sums them."""
    lm = _lm()
    lm.compile()
    assert list(lm.compile_ms) == ["decode", "prefill_b8", "prefill_b16"]
    lm.generate(np.ones((2, 8), np.int32), max_new_tokens=4, fused_chunk=2)
    lm.compile_session_decode_fused(4)
    session = lm.start_session()
    lm.insert(session, np.arange(2), np.ones((2, 8), np.int32))
    assert set(lm.compile_ms) == {
        "decode", "prefill_b8", "prefill_b16", "decode_fused_k2", "session_fused_k4",
        "insert_r2_b8"}
    assert all(ms > 0 for ms in lm.compile_ms.values())


# -------------------------------------------------------------- hit and miss

def test_a_first_compile_misses_and_a_second_lm_loads(cache_dir):
    first = _lm()
    first.compile()
    rows = first.compile_rows()
    assert _sum(rows, "misses") == len(rows) == 3 and _sum(rows, "hits") == 0
    assert all(r["xla_compile_ms"] > 0 and r["cache_load_ms"] == 0 for r in rows)
    assert len(list(cache_dir.iterdir())) >= 3
    jax.clear_caches()                        # the next lm's lowerings reach the cache again
    second = _lm()
    second.compile()
    rows = second.compile_rows()
    assert _sum(rows, "hits") == 3 and _sum(rows, "misses") == 0
    assert all(r["cache_load_ms"] > 0 and r["xla_compile_ms"] == 0 for r in rows)


def test_without_a_cache_neither_event_fires_and_the_stretch_is_the_backends():
    assert not jax.config.jax_compilation_cache_dir      # tests/conftest.py sets none
    lm = _lm()
    lm.compile()
    rows = lm.compile_rows()
    assert _sum(rows, "hits") == _sum(rows, "misses") == 0
    assert all(r["xla_compile_ms"] > 0 and r["cache_load_ms"] == 0 for r in rows)


def test_init_params_row_says_what_the_processs_events_say(cache_dir):
    """The weights' program: its abstract pass, its three stages, and a hit or
    a miss as JAX's own events count it. The key is an ARGUMENT of the program
    (ISSUE 57), so a NEW seed asks the cache for the program the first seed
    wrote and is a hit, as the same seed again is."""
    nxd = neuronx_distributed_config(tensor_parallel_size=1)
    ids = jnp.ones((1, 8), jnp.int32)

    def build(seed):
        nxd["model_init_config"] = {"seed": seed}
        before = (len(compile_log.rows), compile_log.counts(),
                  compile_log.unnamed["hits"], compile_log.unnamed["misses"])
        model = initialize_parallel_model(nxd, lambda: LlamaForCausalLM(LlamaConfig(**TINY)), ids)
        jax.block_until_ready(model.params)
        rows = compile_log.rows[before[0]:]
        assert [(r["name"], r["group"]) for r in rows] == [("init_params", "weights")]
        row = rows[0]
        assert row["abstract_ms"] > 0 and row["trace_lower_ms"] > 0
        stamped = sum(row[p] for p in ("abstract_ms", *PARTS[:3]))
        assert stamped <= row["wall_ms"]
        # whose events: the process's count moved by the row's and by unnamed's
        after = compile_log.counts()
        assert after["cache_hits"] - before[1]["cache_hits"] == \
            row["hits"] + compile_log.unnamed["hits"] - before[2]
        assert after["cache_misses"] - before[1]["cache_misses"] == \
            row["misses"] + compile_log.unnamed["misses"] - before[3]
        assert row["hits"] + row["misses"] == 1
        assert (row["cache_load_ms"] > 0) == (row["hits"] == 1)
        assert (row["xla_compile_ms"] > 0) == (row["misses"] == 1)
        return row, model.params

    cold, params = build(0)
    assert cold["misses"] == 1
    other, drawn = build(1)                   # a new seed loads the first one's program
    assert (other["hits"], other["misses"], other["xla_compile_ms"]) == (1, 0, 0.0)
    assert not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(drawn)))
    jax.clear_caches()
    again, same = build(0)
    assert again["hits"] == 1
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(same)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _startup_stats()["startup_init_ms"] >= int(sum(
        again[p] for p in ("abstract_ms", *PARTS[:3])))


# ------------------------------------------------------------- whose event

def test_an_eager_op_outside_any_row_is_unnamed_with_its_name(own_log, cache_dir):
    def peculiarly_named(x):
        return jnp.cos(x) * 5 + x[::-1]

    x = jnp.arange(7.0)                       # before the log listens
    with own_log.program("opened_and_closed"):
        pass
    before = own_log.counts()
    jax.jit(peculiarly_named)(x)
    unnamed = own_log.unnamed
    assert (unnamed["compiles"], unnamed["misses"], unnamed["hits"]) == (1, 1, 0)
    assert unnamed["ms"] > 0
    assert [name for _, name in unnamed["longest"]] == ["jit(peculiarly_named)"]
    assert own_log.counts() == {"compiles": before["compiles"] + 1, "cache_hits": 0,
                                "cache_misses": before["cache_misses"] + 1}
    assert [dict(r, wall_ms=0) for r in own_log.rows] == [
        {"name": "opened_and_closed", "group": "programs", "wall_ms": 0, "trace_lower_ms": 0.0,
         "xla_compile_ms": 0.0, "cache_load_ms": 0.0, "hits": 0, "misses": 0}]


def test_unnamed_keeps_the_five_longest(own_log):
    own_log.listen()
    for i, secs in enumerate([0.3, 0.1, 0.7, 0.2, 0.5, 0.4, 0.6]):
        own_log._duration(compile_cache._LOWERED, 0.01, fun_name=f"jit(f{i})")
        own_log._duration(compile_cache._BACKEND, secs, fun_name=f"jit(f{i})")
    own_log._duration("/jax/core/compile/jaxpr_trace_duration", 9.0, fun_name="f")   # never summed
    assert own_log.unnamed["compiles"] == 7
    assert own_log.unnamed["ms"] == pytest.approx(2870.0)
    assert [name for _, name in own_log.unnamed["longest"]] == [
        "jit(f2)", "jit(f6)", "jit(f4)", "jit(f5)", "jit(f0)"]
    assert [ms for ms, _ in own_log.unnamed["longest"]] == pytest.approx([700, 600, 500, 400, 300])


def test_an_event_while_no_compile_is_in_flight_is_unnamed_not_the_open_rows(own_log, cache_dir):
    """A program's example arguments are built inside its row and outside its
    ``staged`` compile: theirs is ``unnamed``'s, the program's own the row's."""
    def step(x):
        return x * 3 - 1

    with own_log.program("step") as row:
        x = jax.jit(lambda: jnp.full((11, 3), 2.0))()          # an example argument
        assert (row["hits"], row["misses"]) == (0, 0)
        assert own_log.unnamed["compiles"] == 1 and own_log.unnamed["misses"] == 1
        unnamed_ms = own_log.unnamed["ms"]
        own_log.staged(jax.jit(step), x)
    assert (row["hits"], row["misses"]) == (0, 1) and row["xla_compile_ms"] > 0
    # nothing of the program's own landed in ``unnamed``: no second counted twice
    assert own_log.unnamed["compiles"] == 1 and own_log.unnamed["ms"] == unnamed_ms
    assert own_log.counts() == {"compiles": 2, "cache_hits": 0, "cache_misses": 2}


def test_the_relays_compilations_are_relay_ms_and_not_unnamed():
    """``_hold`` re-lays a leaf with the cache off (``_compiled_afresh``): a
    backend compile and no cache event, inside the row that settles the
    formats."""
    cfg, params = _params()
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16), max_batch=2)
    head_major = jax.tree_util.tree_map_with_path(
        lambda path, leaf: Format(Layout(major_to_minor=(0, 2, 1, 3)), leaf.sharding)
        if jax.tree_util.keystr(path).endswith("['q_kernel']") else None, params)
    before = (compile_log.counts(), dict(compile_log.unnamed))
    with compile_log.program("settles_the_formats") as row:
        lm._hold(head_major)
    assert lm.param_relaid_leaves == 1
    assert row["relay_ms"] > 0 and row["relay_ms"] <= row["wall_ms"]
    assert (row["hits"], row["misses"], row["trace_lower_ms"], row["xla_compile_ms"]) == (
        0, 0, 0.0, 0.0)
    assert compile_log.counts()["compiles"] > before[0]["compiles"]
    assert compile_log.unnamed == before[1]
    assert compile_log.rows[-1] is row
    # outside every row (new weights through the setter) the stretch is nobody's
    n = len(compile_log.rows)
    lm.params = _params(seed=1)[1]
    assert len(compile_log.rows) == n and compile_log.unnamed["compiles"] > before[1]["compiles"]


def test_set_aside_moves_what_was_staged_for_a_program_thrown_away(own_log):
    with own_log.program("decode") as row:
        own_log.staged(jax.jit(lambda x: x + 1), jnp.ones((2,)))
        staged = sum(row[p] for p in PARTS[:3])
        own_log.set_aside("asking_ms")
        assert row["asking_ms"] == pytest.approx(staged) and staged > 0
        assert [row[p] for p in PARTS[:3]] == [0.0] * 3
        own_log.staged(jax.jit(lambda x: x + 2), jnp.ones((2,)))
    assert row["trace_lower_ms"] > 0 and sum(row.get(p, 0.0) for p in PARTS) <= row["wall_ms"]
    assert own_log.sums()["asking_ms"] == row["asking_ms"]


# ------------------------------------------------------ what is read of it

def test_an_engine_built_after_warm_up_mirrors_the_processs_totals():
    lm = _lm(page_size=4)
    lm.compile()
    first = ServeEngine(lm, block_steps=4, rng=jax.random.key(0))
    at_first = {k: first.stats[k] for k in STARTUP_KEYS}
    assert set(STARTUP_KEYS) <= {k for k, _ in first.stats.items()}
    first.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    while first.step_block():                 # the warm-up: insert and fused block compile
        pass
    assert {k: first.stats[k] for k in STARTUP_KEYS} == at_first      # set when built
    engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0))
    got = {k: engine.stats[k] for k in STARTUP_KEYS}
    assert got == _startup_stats() and all(isinstance(v, int) for v in got.values())
    for key in ("startup_trace_lower_ms", "startup_xla_compile_ms"):
        assert got[key] > at_first[key], key
    assert got["startup_unnamed_ms"] >= at_first["startup_unnamed_ms"]
    # the parts are sums over the rows ``compile_ms`` has a key for, and this
    # lm's share of them stays under its own wall
    mine = sum(_sum(lm.compile_rows(), p) for p in PARTS)
    assert mine <= sum(lm.compile_ms.values()) + 0.05 * len(lm.compile_ms)
    assert sum(got[k] for k in STARTUP_KEYS[:4]) >= int(mine)


def test_the_engines_keys_hold_the_weights_program_apart(monkeypatch):
    """``engine.py`` spells the ``startup_*`` keys from the log's neutral sums:
    the rows of ``programs`` for the parts, the rows of ``weights`` for
    ``startup_init_ms``, the whole process's misses."""
    log = CompileLog()
    log.rows += [
        {"name": "decode", "group": "programs", "trace_lower_ms": 1200.6, "xla_compile_ms": 0.0,
         "cache_load_ms": 55.5, "asking_ms": 4000.0, "relay_ms": 250.6, "hits": 4, "misses": 1},
        {"name": "insert_r1_b8", "group": "programs", "trace_lower_ms": 150.0,
         "xla_compile_ms": 2000.0, "cache_load_ms": 0.0, "hits": 0, "misses": 0},
        {"name": "init_params", "group": "weights", "abstract_ms": 10.0, "trace_lower_ms": 50.0,
         "xla_compile_ms": 40.0, "cache_load_ms": 0.0, "hits": 0, "misses": 0}]
    log.unnamed.update(compiles=5, ms=99.6, hits=3, misses=2)
    assert log.sums("weights") == {"abstract_ms": 10.0, "trace_lower_ms": 50.0,
                                   "xla_compile_ms": 40.0, "cache_load_ms": 0.0,
                                   "hits": 0, "misses": 0}
    assert log.sums()["hits"] == 4 and log.sums("nobody's") == {}
    assert log.counts() == {"compiles": 0, "cache_hits": 7, "cache_misses": 3}
    monkeypatch.setattr("neuronx_distributed_tpu.inference.engine.compile_log", log)
    assert _startup_stats() == {
        "startup_trace_lower_ms": 1351, "startup_xla_compile_ms": 2000,
        "startup_cache_load_ms": 56, "startup_format_ms": 4251, "startup_cache_misses": 3,
        "startup_unnamed_ms": 100, "startup_init_ms": 100}
    registered = [k for k in _STAT_KEYS if k.startswith("startup_")]
    assert list(_startup_stats()) == STARTUP_KEYS == registered
    assert not log._listening                 # reading registers nothing


def test_the_span_carries_the_row_and_the_exposition_the_parts():
    lm = _lm(page_size=4)
    lm.compile()
    engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0), trace=True)
    engine.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    engine.run()
    spans = {e["name"]: e for e in engine.tracer.events()
             if e["ph"] == "X" and e["name"].startswith("compile:")}
    built = [r for r in lm.compile_rows() if "compile:" + r["name"] in spans]
    assert built and {r["name"] for r in built} == set(lm.compile_ms) - {"decode"}
    for row in built:
        args = spans["compile:" + row["name"]]["args"]
        assert args["name"] == row["name"]
        assert args["trace_lower_ms"] == pytest.approx(row["trace_lower_ms"], abs=0.01)
        assert {"wall_ms", "xla_compile_ms", "cache_load_ms", "hits", "misses"} <= set(args)
    fams = parse_prometheus(engine.metrics.to_prometheus())
    parts = fams["compile_part_ms"]["samples"]
    for row in lm.compile_rows():
        for part in ("trace_lower", "xla_compile", "cache_load"):
            key = ("compile_part_ms", (("part", part), ("program", row["name"])))
            assert parts[key] == pytest.approx(row[part + "_ms"], abs=0.01), key
        assert fams["compile_cache_misses"]["samples"][
            ("compile_cache_misses", (("program", row["name"]),))] == row["misses"]
    assert set(fams["compile_ms"]["samples"]) >= {
        ("compile_ms", (("program", sig),)) for sig in lm.compile_ms}


# ------------------------------------------------ one pair of listeners

def test_one_pair_of_listeners_however_many_lms_and_engines(monkeypatch, own_log):
    calls = {"event": 0, "duration": 0}

    def counted(kind, register):
        def wrapped(callback):
            calls[kind] += 1
            return register(callback)
        return wrapped

    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        counted("event", jax.monitoring.register_event_listener))
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener",
                        counted("duration", jax.monitoring.register_event_duration_secs_listener))
    for _ in range(3):                        # a log registers on first use, once
        with own_log.program("p"):
            own_log.staged(jax.jit(lambda x: x * 2), jnp.ones((2,)))
    assert calls == {"event": 1, "duration": 1}
    for _ in range(2):                        # the process's: at most once, ever
        lm = _lm(page_size=4)
        lm.compile()
        ServeEngine(lm, block_steps=4, rng=jax.random.key(0))
        ServeEngine(lm, block_steps=4, rng=jax.random.key(1))
    assert calls["event"] <= 2 and calls["duration"] <= 2 and compile_log._listening
    registered = [cb for cb in monitoring.get_event_listeners()
                  if getattr(cb, "__self__", None) is compile_log]
    assert len(registered) == 1


def test_the_package_and_the_smoke_hold_one_registration():
    """``chip_smoke.py`` reads the program's log and holds no listener of its
    own; the benchmark's ``CompileWatch`` is the benchmark's."""
    pattern = re.compile(r"register_event(_duration_secs)?_listener\(")
    holders = [str(p.relative_to(ROOT)) for p in
               [*sorted((ROOT / "neuronx_distributed_tpu").rglob("*.py")), ROOT / "chip_smoke.py"]
               if pattern.search(p.read_text())]
    assert holders == ["neuronx_distributed_tpu/utils/compile_cache.py"]
    assert len(pattern.findall((ROOT / holders[0]).read_text())) == 2
