"""An insert that nobody on the device waits for is fetched late (ISSUE 55).

With nothing decoding at its dispatch, ``ServeEngine._insert_group`` leaves an
insert's first tokens on the device and queues the admission on
``_first_pending``; the sync round settles every pending insert but the newest
it dispatched itself, so the host plans and dispatches insert n+1 while insert
n runs. A row whose budget is one token gives its slot back at the dispatch
and its completion is built when the token arrives. With rows decoding
nothing changes: the fetch is inside the ``admission`` span.

What the tokens are compared with: greedy first tokens are the argmax of
``lm.insert``'s own logits (no engine in it), sampled ones come from an engine
that serves one request at a time (nothing to overlap), longer streams from the
stepwise engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.simlm import SimCausalLM
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
K = 4
B = 3


@pytest.fixture(scope="module")
def base():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return cfg, params


@pytest.fixture(scope="module")
def lm(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(16, 32), max_batch=B, page_size=4,
                    prefix_cache=True).compile()


@pytest.fixture(scope="module")
def slab(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(16, 32), max_batch=B).compile()


def _sim():
    return SimCausalLM(max_batch=B, buckets=(16, 32), max_seq_len=64, vocab_size=128,
                       page_size=4, page_pool_pages=60)


def _prompts(n, seed=0, lo=5, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 128, (int(k),)).astype(np.int32) for k in rng.randint(lo, hi, (n,))]


def _engine(lm, **kw):
    return ServeEngine(lm, block_steps=K, rng=None if isinstance(lm, SimCausalLM)
                       else jax.random.key(7), **kw)


def _streams(engine):
    return {c.request_id: (c.tokens.tolist(), c.finish_reason) for c in engine.completed}


def _greedy_first(lm, prompts):
    """Argmax of the insert program's logits, a prompt at a time."""
    out = []
    for p in prompts:
        session = lm.start_session()
        logits = lm.insert(session, np.asarray([0]), p[None], lengths=np.asarray([p.size]),
                           reserve_tokens=1)
        out.append(int(np.asarray(logits)[0].argmax()))
    return out


class FetchSpy:
    """The order of dispatches and fetches: ``("insert", n)`` where
    ``_insert_group`` returned for the n-th insert, ``("fetch", n)`` where
    ``_fetch_first`` was called with that insert's tokens."""

    def __init__(self, engine):
        self.log, self.number, self.held = [], {}, []      # held: an id stays one array's
        insert, fetch = engine._insert_group, engine._fetch_first

        def spied_insert(*a, **kw):
            out = insert(*a, **kw)
            n = int(engine.stats["inserts"])
            self.held.append(engine.session.first_tokens)
            self.number[id(self.held[-1])] = n
            self.log.append(("insert", n))
            return out

        def spied_fetch(first_dev, routing):
            self.log.append(("fetch", self.number[id(first_dev)]))
            return fetch(first_dev, routing)

        engine._insert_group, engine._fetch_first = spied_insert, spied_fetch


def _closed_loop(engine, prompts, callers, budget=1, sampler=None):
    """``callers`` callers, each sending its next prompt when the last reply is
    back: the harness's closed loop (``benchmark/drivers/serving.py``)."""
    waiting, sent, seen, order = callers, 0, 0, {}
    while sent < len(prompts) or engine.has_decode_work() or len(engine.queue):
        while waiting and sent < len(prompts):
            rid = engine.submit(prompts[sent], max_new_tokens=budget, sampler=sampler,
                                arrival_block=engine.blocks)
            order[rid] = sent
            sent, waiting = sent + 1, waiting - 1
        engine.step_block()
        waiting += len(engine.completed) - seen
        seen = len(engine.completed)
    assert not engine.step_block()
    return order


# (a) --------------------------------------------------------------------------

@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_a_closed_loop_of_one_token_requests_overlaps_its_inserts(lm, slab, cache):
    model = lm if cache == "paged" else slab
    prompts = _prompts(8 * B, seed=1)
    engine = _engine(model)
    spy = FetchSpy(engine)
    order = _closed_loop(engine, prompts, callers=2 * B)
    got = {order[rid]: toks for rid, (toks, _) in _streams(engine).items()}
    want = _greedy_first(model, prompts)
    assert got == {i: [t] for i, t in enumerate(want)}
    assert all(c.finish_reason == "budget" and c.decode_blocks == 0 for c in engine.completed)
    stats = engine.stats
    assert stats["decode_blocks"] == 0 and stats["completed"] == len(prompts)
    assert stats["insert_fetches_deferred"] == stats["inserts"] == stats["insert_host_fetches"]
    assert stats["slots_released_at_dispatch"] == len(prompts)
    # every insert but the first was dispatched with an earlier one unfetched ...
    assert stats["inserts_overlapped"] >= stats["inserts"] - 2 > 0
    # ... and the spy saw it: insert n+1 returns before insert n is fetched,
    # each insert is fetched once, in order, and never more than two are out
    fetched = [n for kind, n in spy.log if kind == "fetch"]
    assert fetched == sorted(set(fetched)) == list(range(1, int(stats["inserts"]) + 1))
    overlapped = sum(spy.log.index(("insert", n + 1)) < spy.log.index(("fetch", n))
                     for n in fetched[:-1])
    assert overlapped == stats["inserts_overlapped"]
    out = 0
    for kind, _ in spy.log:
        out += 1 if kind == "insert" else -1
        assert 0 <= out <= 2
    assert not engine._first_pending and all(r is None for r in engine.slots)
    if model.paged:
        # pages: no slot holds one, every table row points back at scratch,
        # and what is still in use is what the prefix index keeps
        pkv = engine.session.paged
        assert pkv.live_pages() == []
        assert np.array_equal(pkv.tables, np.broadcast_to(pkv.scratch[:, None], pkv.tables.shape))
        assert pkv.allocator.in_use() == pkv.prefix.evictable_pages()


def test_sampled_one_token_replies_are_the_ones_served_one_at_a_time(lm):
    prompts = _prompts(4 * B, seed=2)
    hot = Sampler(temperature=0.9)
    alone = _engine(lm)
    for p in prompts:
        alone.submit(p, max_new_tokens=1, sampler=hot, arrival_block=alone.blocks)
        alone.run()
    assert alone.stats["inserts_overlapped"] == 0
    loop = _engine(lm)
    order = _closed_loop(loop, prompts, callers=2 * B, sampler=hot)
    assert loop.stats["inserts_overlapped"] > 0
    # the closed loop numbers its requests in submission order too
    assert [order[r] for r in sorted(order)] == list(range(len(prompts)))
    assert _streams(loop) == _streams(alone)


def test_a_backlog_keeps_two_inserts_out_and_no_more(lm):
    """Five groups queued at once: each dispatch first fetches all but the
    newest pending insert, so the device's queue never holds a third."""
    engine = _engine(lm)
    spy = FetchSpy(engine)
    prompts = _prompts(5 * B, seed=3)
    for p in prompts:
        engine.submit(p, max_new_tokens=1, arrival_block=0)
    assert engine.step_block() and len(engine._first_pending) == B     # the newest stays out
    assert engine.stats["inserts"] == 5 and engine.stats["insert_host_fetches"] == 4
    out = 0
    for kind, _ in spy.log:
        out += 1 if kind == "insert" else -1
        assert 0 <= out <= 2
    assert engine.step_block() and len(engine.completed) == len(prompts)
    assert not engine.step_block()
    assert [c.tokens.tolist() for c in engine.completed] == [[t] for t in _greedy_first(lm, prompts)]


# (b) --------------------------------------------------------------------------

@pytest.mark.parametrize("first", ["one_token_rows_first", "long_rows_first"])
def test_mixed_budgets_in_one_insert(lm, first):
    """The one-token rows free their slots at dispatch, the others keep theirs
    and decode in the same round; streams are the stepwise engine's."""
    prompts = [p[:9] for p in _prompts(2 * B, seed=4, lo=9, hi=15)]     # one bucket
    budgets = [1, 7, 1, 1, 6, 1] if first == "one_token_rows_first" else [7, 1, 9, 1, 1, 6]
    engines = {}
    for name, kw in (("fused", {}), ("stepwise", {"fused": False})):
        engine = _engine(lm, **kw)
        for p, n in zip(prompts[:B], budgets[:B]):
            engine.submit(p, max_new_tokens=n, eos_token_id=None, arrival_block=0)
        engine.step_block()
        if name == "fused":
            ones = budgets[:B].count(1)
            assert engine.stats["insert_fetches_deferred"] == 1
            assert engine.stats["slots_released_at_dispatch"] == ones
            # settled before the block launched: the one-token replies are in,
            # the others hold their slots and have decoded a block
            assert not engine._first_pending
            assert sum(len(c.tokens) == 1 for c in engine.completed) == ones
            assert sum(r is not None for r in engine.slots) == B - ones
            assert engine.stats["decode_blocks"] == 1
        for p, n in zip(prompts[B:], budgets[B:]):
            engine.submit(p, max_new_tokens=n, arrival_block=engine.blocks)
        engine.run()
        assert not engine._first_pending
        engines[name] = engine
    assert _streams(engines["fused"]) == _streams(engines["stepwise"])
    assert sorted(len(t) for t, _ in _streams(engines["fused"]).values()) == sorted(budgets)


def test_a_first_token_that_ends_a_longer_stream_retires_it_before_the_launch(lm):
    prompt = _prompts(1, seed=5)[0]
    eos = _greedy_first(lm, [prompt])[0]
    engine = _engine(lm)
    engine.submit(prompt, max_new_tokens=9, eos_token_id=eos)
    assert not engine.step_block()          # settled, retired, nothing launched
    assert engine.stats["insert_fetches_deferred"] == 1 and engine.stats["decode_blocks"] == 0
    assert _streams(engine) == {0: ([eos], "eos")}


def test_a_one_token_reply_that_is_the_eos_says_so(lm):
    prompts = _prompts(2, seed=6)
    eos = _greedy_first(lm, prompts)[0]
    engine = _engine(lm)
    for p in prompts:
        engine.submit(p, max_new_tokens=1, eos_token_id=eos)
    engine.run()
    want = _greedy_first(lm, prompts)
    assert _streams(engine) == {0: ([want[0]], "eos"),
                                1: ([want[1]], "eos" if want[1] == eos else "budget")}


# (c) --------------------------------------------------------------------------

def test_with_a_row_decoding_nothing_is_deferred(lm):
    engine = _engine(lm, trace=True)
    prompts = _prompts(3, seed=8)
    engine.submit(prompts[0], max_new_tokens=3 * K, arrival_block=0)
    engine.step_block()
    deferred, fetches = engine.stats["insert_fetches_deferred"], engine.stats["insert_host_fetches"]
    assert deferred == 1                 # into the empty engine: nobody waited
    calls = []
    fetch = engine._fetch_first
    engine._fetch_first = lambda *a: (calls.append(len(engine._first_pending)), fetch(*a))[1]
    for p in prompts[1:]:
        engine.submit(p, max_new_tokens=1, arrival_block=engine.blocks)
    engine.step_block()
    assert engine.stats["insert_fetches_deferred"] == deferred
    assert engine.stats["insert_host_fetches"] == fetches + 1 and calls == [0]
    assert engine.stats["slots_released_at_dispatch"] == 0 and len(engine.completed) == 2
    spans = [e for e in engine.tracer.events() if e["ph"] == "X" and e["name"] == "admission"]
    assert [e["args"]["decoding"] for e in spans] == [0, 1]
    first, last = [e for e in engine.tracer.events()
                   if e["ph"] == "X" and e["name"] == "insert_fetch"]
    # the fetch lies inside the stalling admission's span, as it always did
    assert spans[1]["ts"] <= last["ts"] and last["ts"] + last["dur"] <= spans[1]["ts"] + spans[1]["dur"]
    assert first["ts"] >= spans[0]["ts"] + spans[0]["dur"]       # the deferred one: after its span
    engine.run()
    assert len(engine.completed) == 3


# (d) --------------------------------------------------------------------------

def test_step_block_says_true_while_a_reply_is_pending_and_run_ends_with_none(lm):
    engine = _engine(lm)
    for p in _prompts(B, seed=9):
        engine.submit(p, max_new_tokens=1)
    assert engine.step_block() is True and len(engine._first_pending) == B
    assert engine.blocks == 0 and not engine.completed and engine.has_decode_work()
    assert all(r is None for r in engine.slots)              # the slots are free already
    # the round that brings the replies says True as well: a caller that reads
    # completions after a True (the benchmark's drain) sees them
    assert engine.step_block() is True and not engine._first_pending
    assert len(engine.completed) == B and engine.blocks == 0
    assert engine.step_block() is False and not engine.has_decode_work()
    # the benchmark's drain: stop at the first False, read completions after a True
    drained = _engine(lm)
    for p in _prompts(2 * B, seed=9):
        drained.submit(p, max_new_tokens=1)
    seen = 0
    while drained.step_block():
        seen = len(drained.completed)
    assert seen == 2 * B
    again = _engine(lm)
    for p in _prompts(3 * B, seed=9):
        again.submit(p, max_new_tokens=1)
    assert len(again.run()) == 3 * B and not again._first_pending and not again.has_decode_work()


def _pending(lm, **kw):
    engine = _engine(lm, **kw)
    rids = [engine.submit(p, max_new_tokens=1, **({"deadline_ms": 1.0} if kw.get("block_time_ms") else {}))
            for p in _prompts(B, seed=10)]
    assert engine.step_block() and len(engine._first_pending) == B
    return engine, rids


@pytest.mark.parametrize("how", ["snapshot", "cancel", "cancel_other", "park", "deadline"])
def test_a_pending_one_token_reply_is_settled_not_lost(lm, how, tmp_path):
    want = [[t] for t in _greedy_first(lm, _prompts(B, seed=10))]
    if how == "snapshot":
        engine, rids = _pending(lm)
        snap = engine.snapshot()
        assert not engine._first_pending and not snap["requests"]
    elif how == "cancel":
        engine, rids = _pending(lm)
        # the reply was dispatched for the caller: it completes, as it would
        # have inside its round, and there is nothing left to cancel
        assert engine.cancel(rids[1]) is False
        assert not engine._first_pending and engine.stats["cancelled"] == 0
    elif how == "cancel_other":
        engine, rids = _pending(lm)
        queued = engine.submit(_prompts(1, seed=11)[0], max_new_tokens=4, arrival_block=5)
        assert engine.cancel(queued) is True
        assert len(engine._first_pending) == B          # a queued request's cancel fetches nothing
        engine.run()
    elif how == "park":
        engine, rids = _pending(lm, park_dir=str(tmp_path))
        with pytest.raises(ValueError, match="not a decoding stream"):
            engine.park(rids[0])                        # it holds no slot: its stream is over
        engine.run()
    else:
        engine, rids = _pending(lm, block_time_ms=1.0)
        # a later request keeps virtual time moving past the first ones' deadline
        engine.submit(_prompts(1, seed=11)[0], max_new_tokens=1, arrival_block=4)
        engine.run()
        assert engine.stats["expired"] == 0 and engine.stats["deadline_misses"] == 0
        assert len(engine.completed) == B + 1
    done = {c.request_id: c for c in engine.completed}
    assert [done[r].tokens.tolist() for r in rids] == want
    assert all(not (done[r].expired or done[r].cancelled or done[r].deadline_missed)
               and done[r].decode_blocks == 0 for r in rids)
    assert not engine._first_pending


# (e) --------------------------------------------------------------------------

def test_a_prefill_worker_never_defers(lm):
    engine = _engine(lm, role="prefill")
    for p, n in zip(_prompts(B, seed=12), (1, 5, 1)):
        engine.submit(p, max_new_tokens=n)
    engine.step_block()
    assert engine.stats["insert_fetches_deferred"] == 0
    assert engine.stats["slots_released_at_dispatch"] == 0 and not engine._first_pending
    assert engine.stats["insert_host_fetches"] == engine.stats["inserts"] == 1
    assert len(engine.completed) == 2 and len(engine.outbox) == 1


# sim parity --------------------------------------------------------------------

@pytest.mark.parametrize("loop", ["sync", "async"])
def test_sim_and_real_engines_keep_one_schedule(lm, loop):
    """One-token and longer budgets through the same arrivals: the sim
    engine's per-request blocks equal the real engine's, in either loop."""
    prompts = _prompts(4 * B, seed=13)
    budgets = [1, 1, 1, 1, 6, 1, 1, 1, 9, 1, 1, 1]
    scheds = {}
    for name, model in (("real", lm), ("sim", _sim())):
        engine = _engine(model, async_loop=loop == "async")
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            engine.submit(p, max_new_tokens=n, arrival_block=i // 5)
        engine.run()
        assert not engine._first_pending
        scheds[name] = sorted((c.request_id, c.queue_blocks, c.ttft_blocks, c.decode_blocks,
                               len(c.tokens)) for c in engine.completed)
        scheds[name + "_stats"] = {k: engine.stats[k] for k in (
            "inserts", "insert_fetches_deferred", "inserts_overlapped",
            "slots_released_at_dispatch", "decode_blocks", "blocks")}
    assert scheds["real"] == scheds["sim"] and scheds["real_stats"] == scheds["sim_stats"]


def test_the_async_loop_shares_the_mechanism(lm):
    """One deferred-first mechanism: the async loop's one-token rows leave
    their slots at dispatch too, and its streams are the sync loop's."""
    prompts = _prompts(3 * B, seed=14)
    budgets = [1, 7, 1, 1, 1, 5, 1, 1, 1]
    out = {}
    for loop in (False, True):
        engine = _engine(lm, async_loop=loop)
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            engine.submit(p, max_new_tokens=n, arrival_block=i // 4,
                          sampler=Sampler(temperature=0.7) if i % 2 else None)
        engine.run()
        assert not engine._first_pending and not engine._inflight
        assert engine.stats["slots_released_at_dispatch"] > 0
        out[loop] = _streams(engine)
    assert out[True] == out[False]
