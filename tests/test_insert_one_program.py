"""An insert is one program and one fetch.

The scheduler's admission of a group (``ServeEngine._insert_group``) costs the
host ONE compiled-program call and ONE fetch: the insert program runs the head
over each row's last real position only, derives the rows' request keys, writes
them into the session's ``slot_keys`` and samples token index 0 of each
request's stream; the engine fetches the first tokens (and a model with
experts' routing sums) together. Counted here independently of the engine's
own stats, as ``tests/helpers.py`` does for the decode block: the tracer's
spans, a wrapper around the compiled program, a wrapper around
``jax.device_get``, and JAX's own monitoring events for every OTHER
computation (``jax.clear_caches()`` first, so that any eager op launched inside
``_insert_group`` has to be traced and compiled anew, and says so).

Values, on the CPU in float32: the first tokens, the request keys and the
``(rows, vocab)`` logits are what the eager code they replace produced.
"""

import contextlib
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import tiny
from tests.helpers import count_factory_calls, dispatch_counts
from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.causal_lm import FirstToken
from neuronx_distributed_tpu.inference.sampling import SlotSampler
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, OlmoeForCausalLM

TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
MODELS = {
    "dense": (LlamaConfig(**TINY), LlamaForCausalLM),
    "experts": (OlmoeConfig(**dict(TINY, num_kv_heads=4, intermediate_size=32, num_experts=8,
                                   top_k=2)), OlmoeForCausalLM),
}
B, BUCKET, PAGE = 8, 16, 4


def _lm(model="dense", paged=True, grammar=False):
    """One compiled stack per (model, cache form, grammar support)."""
    cfg, cls = MODELS[model]
    params = tiny.built(("insert_one_program", model), lambda: tiny.make_params(cls, cfg, seed=0))
    return tiny.built(("insert_one_program", model, paged, grammar), lambda: CausalLM(
        cfg, params, cls, buckets=(BUCKET, 32), max_batch=B,
        **(dict(page_size=PAGE) if paged else {}),
        **(dict(grammar_slots=3, grammar_states=48) if grammar else {})).compile())


def _prompts(n, seed, lo=5, hi=BUCKET):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 127, (int(k),)).astype(np.int32)
            for k in rng.randint(lo, hi + 1, (n,))]


# ---------------------------------------------------------------- the contract

class _Launches:
    """JAX computations traced or compiled while ``on``: after
    ``jax.clear_caches()`` every eager op is one of each."""

    def __init__(self):
        self.on, self.seen = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name in ("/jax/core/compile/jaxpr_trace_duration",
                                "/jax/core/compile/backend_compile_duration"):
            self.seen.append((name, kw.get("fun_name")))

    @contextlib.contextmanager
    def inside(self, obj, attr):
        """Watch for the length of every call of ``obj.attr``."""
        orig = getattr(obj, attr)

        def wrapped(*a, **kw):
            self.on = True
            try:
                return orig(*a, **kw)
            finally:
                self.on = False

        setattr(obj, attr, wrapped)
        try:
            yield self
        finally:
            setattr(obj, attr, orig)


LAUNCHES = _Launches()


class _InsertSpy:
    """Calls of the insert programs of ``lm``, and host reads of what they
    returned (``jax.device_get``, ``np.asarray``, ``np.array`` of any output
    leaf), counted where they happen: not from the engine's stats or spans."""

    def __init__(self, lm):
        self.lm, self.calls, self.reads, self.outputs = lm, 0, 0, []

    def _program(self, compiled):
        def run(*args):
            self.calls += 1
            out = compiled(*args)
            self.outputs.extend(jax.tree.leaves(out))
            return out
        return run

    def _reader(self, fn):
        def read(x, *rest, **kw):
            mine = {id(o) for o in self.outputs}
            self.reads += any(id(leaf) in mine for leaf in jax.tree.leaves(x))
            return fn(x, *rest, **kw)
        return read

    def __enter__(self):
        factory = "_paged_insert_programs" if self.lm.paged else "_insert_programs"
        self._saved = [(self.lm, factory, getattr(self.lm, factory)),
                       (jax, "device_get", jax.device_get),
                       (np, "asarray", np.asarray), (np, "array", np.array)]
        build = self._saved[0][2]
        setattr(self.lm, factory, lambda *a, **kw: self._program(build(*a, **kw)))
        for obj, name, fn in self._saved[1:]:
            setattr(obj, name, self._reader(fn))
        return self

    def __exit__(self, *exc):
        for obj, name, fn in self._saved:
            setattr(obj, name, fn)


def test_the_watch_sees_an_eager_op():
    """The instrument is not blind: one eager add inside a watched call is
    seen, traced and compiled, once the caches are cleared."""
    holder = type("H", (), {"op": staticmethod(lambda: jnp.add(jnp.ones((3,)), 1.0))})
    holder.op()
    jax.clear_caches()
    LAUNCHES.seen.clear()
    with LAUNCHES.inside(holder, "op"):
        holder.op()
    assert len(LAUNCHES.seen) >= 2, LAUNCHES.seen


CONTRACT = list(itertools.product(["paged", "slab"], [1, B], ["dense", "experts"],
                                  ["plain", "grammar_engine"], ["sync", "async"]))


@pytest.mark.parametrize("cache,rows,model,grammar,loop", CONTRACT,
                         ids=["-".join(map(str, c)) for c in CONTRACT])
def test_an_insert_is_one_program_call_and_one_fetch(cache, rows, model, grammar, loop):
    lm = _lm(model, cache == "paged", grammar == "grammar_engine")
    engine = ServeEngine(lm, block_steps=2, rng=jax.random.key(3), trace=True,
                         async_loop=loop == "async")
    if lm.grammar:
        engine.register_grammar("gnum", regex="-?[0-9]{1,3}")

    def admit(seed):
        for p in _prompts(rows, seed):
            engine.submit(p, max_new_tokens=1, sampler=Sampler(temperature=0.8))
        while engine.step_block():
            pass

    admit(seed=rows)                    # the group's program compiles here
    before = (dict(dispatch_counts(engine)), dict(engine.stats.items()), len(engine.completed))
    jax.clear_caches()
    LAUNCHES.seen.clear()
    with _InsertSpy(lm) as spy, LAUNCHES.inside(engine, "_insert_group"):
        admit(seed=100 + rows)
    spans, stats = dispatch_counts(engine), engine.stats
    inserts = stats["inserts"] - before[1]["inserts"]
    assert inserts == 1 and len(engine.completed) - before[2] == rows
    assert spans["insert"] - before[0]["insert"] == inserts
    assert spans["insert_fetch"] - before[0]["insert_fetch"] == inserts
    assert spy.calls == inserts and spy.reads == inserts
    assert stats["insert_program_calls"] + stats["insert_host_fetches"] == 2 * stats["inserts"]
    # nothing else ran on the device for this admission: no eager op was
    # traced or compiled inside _insert_group
    assert not LAUNCHES.seen, LAUNCHES.seen
    if model == "experts" and lm.paged:
        assert stats["moe_insert_layer_calls"] == 2 * stats["inserts"]


def test_insert_programs_fresh_counts_the_calls_whose_rows_all_start_at_zero():
    """Beside ``insert_program_calls``: equal to it on prompts that share
    nothing, smaller by the inserts that a prefix hit started past 0 and by
    the later chunks of a chunked prompt; in ``stats`` from construction."""
    lm = _lm("dense", paged=True)
    engine = ServeEngine(lm, block_steps=2, rng=jax.random.key(3))
    assert engine.stats["insert_programs_fresh"] == 0

    def admit(prompts):
        for p in prompts:
            engine.submit(p, max_new_tokens=1)
            while engine.step_block():
                pass
        return engine.stats["insert_program_calls"], engine.stats["insert_programs_fresh"]

    alone = _prompts(3, seed=41, lo=9)
    assert admit(alone) == (3, 3)
    hits = engine.session.paged.stats["prefix_hits"]
    tail = np.arange(1, 6, dtype=np.int32)
    sharers = [np.concatenate([p[:2 * PAGE], tail]) for p in alone[:2]]
    assert admit(sharers + _prompts(1, seed=43)) == (6, 4)
    assert engine.session.paged.stats["prefix_hits"] - hits == 2
    chunked = ServeEngine(lm, block_steps=2, rng=jax.random.key(3), prefill_chunk_tokens=5)
    chunked.submit(_prompts(1, seed=47, lo=12, hi=12)[0], max_new_tokens=1)
    while chunked.step_block():
        pass
    stats = chunked.stats
    assert (stats["insert_program_calls"], stats["insert_programs_fresh"]) == (3, 1)


# ------------------------------------------------------------------ the values

def _eager_first_tokens(lm, logits, rng, rids, temps, greedy, allowed=None):
    """What ``_insert_group`` computed on the host before: one eager op at a
    time, ``SlotSampler`` under ``fold_in(fold_in(rng, request_id), 0)``."""
    keys = jnp.stack([jax.random.fold_in(rng, int(r)) for r in rids])
    sub = jax.vmap(jax.random.fold_in)(keys, jnp.zeros((len(rids),), jnp.int32))
    if allowed is not None:
        logits = jnp.asarray(np.where(allowed, np.asarray(logits, np.float32), np.float32(-1e30)))
    return np.asarray(SlotSampler()(logits, sub, jnp.asarray(temps), jnp.asarray(greedy))), keys


@pytest.mark.parametrize("loop", ["sync", "async"])
@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_first_tokens_and_slot_keys_equal_the_eager_oracle(cache, loop):
    """A mixed group (two greedy rows, sampled rows at two temperatures, a
    grammar-constrained row) admitted as ONE insert: its first tokens equal
    the eager oracle's bit for bit, and ``slot_keys`` is what the per-row
    ``.at[slot].set`` left, so the decode block's streams are unchanged."""
    lm = _lm("dense", cache == "paged", grammar=True)
    rng = jax.random.key(11)
    engine = ServeEngine(lm, block_steps=2, rng=rng, async_loop=loop == "async")
    engine.register_grammar("gnum", regex="-?[0-9]{1,3}")
    prompts = [p[:BUCKET] for p in _prompts(5, seed=21, lo=9)]
    knobs = [dict(), dict(sampler=Sampler(temperature=0.7)), dict(),
             dict(sampler=Sampler(temperature=1.4)),
             dict(sampler=Sampler(temperature=0.9), grammar="gnum")]
    budget = 3
    rids = [engine.submit(p, max_new_tokens=budget, **kw) for p, kw in zip(prompts, knobs)]
    before = np.asarray(jax.random.key_data(engine._slot_keys))
    slots, insert_group = {}, engine._insert_group

    def spy(group, slot_ids, bucket):
        slots.update({r.request_id: s for r, s in zip(group, slot_ids)})
        return insert_group(group, slot_ids, bucket)

    engine._insert_group = spy
    engine.step_block()
    assert engine.stats["inserts"] == 1 and sorted(slots) == sorted(rids)
    after = np.asarray(jax.random.key_data(engine._slot_keys))
    while engine.step_block():
        pass
    got = {c.request_id: c.tokens[0] for c in engine.completed}

    # the oracle: the logits lm.insert() hands back, sampled eagerly
    session = lm.start_session()
    ids = np.zeros((5, BUCKET), np.int32)
    lens = np.asarray([p.size for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        ids[i, : p.size] = p
    logits = lm.insert(session, np.arange(5), ids, lengths=lens,
                       **(dict(reserve_tokens=budget + 4) if lm.paged else {}))
    allowed = np.ones((5, lm.config.vocab_size), bool)
    allowed[4] = engine.session.grammars.grammar("gnum").allowed_row(0, budget - 1)
    assert not allowed[4].all()
    temps = np.asarray([1.0, 0.7, 1.0, 1.4, 0.9], np.float32)
    greedy = np.asarray([True, False, True, False, False])
    want, keys = _eager_first_tokens(lm, logits, rng, rids, temps, greedy, allowed)
    assert [got[r] for r in rids] == want.tolist()
    assert allowed[4][got[rids[4]]]
    expect = jnp.asarray(before)
    for rid, key in zip(rids, keys):
        expect = expect.at[slots[rid]].set(jax.random.key_data(key))
    np.testing.assert_array_equal(after, np.asarray(expect))


@pytest.mark.parametrize("model", ["dense", "experts"])
@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_insert_logits_are_the_full_forwards_last_position(cache, model):
    """``lm.insert`` returns ``(rows, vocab)``: each row's logits at its last
    real position, as the model's plain forward over the whole prompt gives
    them. Paged: a second insert whose prompts share two pages with the first
    (non-zero ``starts``, a shorter suffix bucket) reads the same logits."""
    lm = _lm(model, cache == "paged")
    cfg, cls = MODELS[model]
    prompts = _prompts(3, seed=31, lo=18, hi=30)
    lens = np.asarray([p.size for p in prompts], np.int32)
    ids = np.zeros((3, 32), np.int32)
    for i, p in enumerate(prompts):
        ids[i, : p.size] = p
    if model == "experts":      # the plain forward drops no assignment
        cfg = dataclasses.replace(cfg, moe_mode="all_experts")
    full = np.asarray(cls(cfg).apply({"params": lm.params}, jnp.asarray(ids)))
    want = full[np.arange(3), lens - 1]
    session = lm.start_session()
    kw = dict(reserve_tokens=2) if lm.paged else {}
    got = lm.insert(session, np.asarray([4, 0, 6]), ids, lengths=lens, **kw)
    assert got.shape == (3, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got).argmax(-1), want.argmax(-1))
    if lm.paged:
        hits = session.paged.stats["prefix_hits"]
        again = lm.insert(session, np.asarray([1, 2, 3]), ids, lengths=lens, **kw)
        assert session.paged.stats["prefix_hits"] > hits
        np.testing.assert_allclose(np.asarray(again), want, rtol=0, atol=2e-5)


def test_default_first_token_inputs_share_the_engines_programs():
    """``lm.insert`` without sampling inputs (the benchmark's reference
    probe, every direct caller) runs the program the engine runs: one per
    (rows, bucket); an engine-wide top-k sampler has its own."""
    lm = _lm("dense", paged=True)
    n = len(lm._paged_insert)
    session = lm.start_session()
    p = _prompts(1, seed=41)[0]
    ids = np.zeros((1, BUCKET), np.int32)
    ids[0, : p.size] = p
    lm.insert(session, np.asarray([0]), ids, lengths=np.asarray([p.size]), reserve_tokens=2)
    assert (1, BUCKET) in lm._paged_insert
    prog = lm._paged_insert_programs(1, BUCKET)
    engine = ServeEngine(lm, block_steps=2, rng=jax.random.key(1))
    with count_factory_calls(lm, "_paged_insert_programs") as calls:
        engine.submit(_prompts(1, seed=42)[0], max_new_tokens=1)
        engine.step_block()
    assert calls.n == 1 and lm._paged_insert_programs(1, BUCKET) is prog
    assert len(lm._paged_insert) <= n + 1
    first = FirstToken(jax.random.key(0), np.zeros((1,), np.uint32), np.ones((1,), np.float32),
                       np.zeros((1,), bool), sampler=SlotSampler(top_k=5))
    lm.retire(session, [0])
    lm.insert(session, np.asarray([0]), ids, lengths=np.asarray([p.size]), reserve_tokens=2,
              first=first)
    assert (1, BUCKET, SlotSampler(top_k=5)) in lm._paged_insert
