"""Of how many ROWS a one-token decode step reads the cache (ISSUE 40): the
second half of ``tests/test_decode_extent.py``, whose docstring says what is
compared with what, in a file of its own so that the two halves run in two
workers (they share no compiled program: these build eight rows, those four).

Two bugs the row bound can have are planted here: a rung of rows one row
short of the live ones, and rows handed back in the walk's order instead of
the step's. Beside the oracle stands the parent's read ("every_row": every
row of the batch inside the bound, no order, one rung), which what a live row
computes and writes must EQUAL, bit for bit.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM
from neuronx_distributed_tpu.models import llama
from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Config
from neuronx_distributed_tpu.models.llama import KVWalk, LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel import mesh as psm
from tests import tiny
from tests.test_decode_extent import (
    CASES,
    K,
    LATENT,
    PAGE,
    TINY,
    build,
    chunk_of,
    close,
    ladder,
    live_steps,
    loops,
    pages_of,
    run_block,
    sums,
    walked,
)

ROWS = 8
# who is live, by name: the first n of this order for "live_n"
SPREAD = [6, 3, 0, 7, 1, 4, 2, 5]
PATTERNS = ["interleaved", "live_1", "live_2", "live_3", "live_4", "live_5", "live_8",
            "equal_reach", "done_longer", "retired_scratch", "edge_and_finish"]
# every form, head layout, page dtype and world once; the whole matrix runs two patterns
ROW_CASES = ["gqa-f32-loop", "gqa-f32-switch", "mha_qknorm-f32-switch", "mqa-int8-switch",
             "gqa-slab-loop", "gqa-f32-loop-tp2", "latent-f32"]


def pattern(lm, name):
    """``(lengths, active, done, mapped)`` of eight rows whose lengths lie in
    four chunks of the table, none in row order."""
    c, seq = chunk_of(lm), lm.config.max_seq_len
    lengths = np.asarray([c + 40, 3 * c + 17, 70, 2 * c + 5, 3 * c + 90, 9, c - 1, 2 * c + c // 2],
                         np.int32)
    active, done, mapped = np.zeros((ROWS,), bool), np.zeros((ROWS,), bool), np.ones((ROWS,), bool)
    if name == "interleaved":
        active[[1, 4]] = True
    elif name.startswith("live_"):
        active[SPREAD[:int(name[5:])]] = True
    elif name == "equal_reach":           # rows 3 and 7 reach as far; 0 is shorter
        lengths[7] = lengths[3]
        active[[0, 3, 7]] = True
    elif name == "done_longer":           # the longest row is done: nobody reads it
        active[[0, 3, 4]] = True
        done[4] = True
    elif name == "retired_scratch":       # a stale long index over a table of scratch
        lengths[1], mapped[1] = seq - 60, False
        active[[2, 6, 7]] = True
    elif name == "edge_and_finish":       # row 0 crosses a chunk edge, row 1 fills its table
        lengths[0], lengths[1] = c - 2, seq - 3
        active[[0, 1, 5]] = True
    return lengths, active, done, mapped


def through(lm, lengths, active, done):
    """Rows live at every step of the block."""
    return np.asarray(active) & ~np.asarray(done) & (np.asarray(lengths) + K + 1 < lm.config.max_seq_len)


def compare_rows(case, name, against):
    """One block of ``name`` by the program's own read and by ``against``:
    the streams of the rows live at the start, the pages of the rows live
    throughout, and the program's sums against the Python model."""
    lm, bounded = build(case, "bounded", fused=True, rows=ROWS)
    spec = pattern(lm, name)
    got = run_block(lm, bounded, *spec)
    lm, other = build(case, against, fused=True, rows=ROWS)
    want = run_block(lm, other, *spec)
    lengths, active, done, _ = spec
    live = active & ~done
    assert np.isfinite(got[0]).all() and all(np.isfinite(p).all() for p in got[1].values())
    assert (got[0][:, live] == want[0][:, live]).all()
    for row in np.nonzero(through(lm, lengths, active, done))[0] if lm.paged else []:
        mine, theirs = pages_of(lm, got[1], row), pages_of(lm, want[1], row)
        for leaf in mine:
            if against == "every_row":
                assert np.array_equal(mine[leaf], theirs[leaf]), (leaf, row)
            elif lm.config.page_dtype == "int8":
                assert np.abs(mine[leaf] - theirs[leaf]).max() <= 1, (leaf, row)
            else:
                assert close(mine[leaf], theirs[leaf], lm.config.dtype), (leaf, row)
    assert got[2].tolist() == sums(lm, live_steps(lm, lengths, active, done))
    return lm, got, want


@pytest.mark.parametrize("name", ["interleaved", "live_5"])
@pytest.mark.parametrize("case", list(CASES))
def test_live_rows_among_dead_ones_give_the_whole_reads_block(case, name):
    """Two live rows between six that are not, and five of eight (the top
    rung): every form, head layout, page dtype and world against the oracle."""
    compare_rows(case, name, "whole")


@pytest.mark.parametrize("name", PATTERNS)
@pytest.mark.parametrize("case", ROW_CASES)
def test_a_live_row_computes_what_the_parents_read_gave_it(case, name):
    """Against the parent's read (every row inside the bound, in place): each
    rung's edge (1, 2, 3, 4, 5, 8 live), two rows of one reach, a done row
    longer than every live one, a retired slot over scratch, a row crossing a
    chunk edge beside one that finishes inside the block. Bit for bit."""
    lm, got, want = compare_rows(case, name, "every_row")
    if name == "live_8":       # the top rung reads what the parent read
        assert got[2][2] == got[2][0] * ROWS
    if not lm.paged:           # the slab has no pages to tell rows by: the whole leaves
        rows = through(lm, *pattern(lm, name)[:3])
        for leaf, pool in got[1].items():
            layers = pool.shape[0] // ROWS
            for row in np.nonzero(rows)[0]:
                assert np.array_equal(pool[row::ROWS][:layers], want[1][leaf][row::ROWS][:layers]), leaf


@pytest.mark.parametrize("name", ["interleaved", "live_5", "edge_and_finish"])
@pytest.mark.parametrize("case", ["gqa-f32-loop", "gqa-f32-switch", "latent-f32"])
def test_the_oracle_agrees_with_the_parents_read(case, name):
    """The two references against each other, so that neither test above
    passes by sharing a fault with its reference."""
    lm, every_row = build(case, "every_row", fused=True, rows=ROWS)
    spec = pattern(lm, name)
    got = run_block(lm, every_row, *spec)
    lm, whole = build(case, "whole", fused=True, rows=ROWS)
    want = run_block(lm, whole, *spec)
    live = spec[1] & ~spec[2]
    assert (got[0][:, live] == want[0][:, live]).all()
    for row in np.nonzero(through(lm, *spec[:3]))[0]:
        mine, theirs = pages_of(lm, got[1], row), pages_of(lm, want[1], row)
        assert all(close(mine[leaf], theirs[leaf], lm.config.dtype) for leaf in mine)


@pytest.mark.parametrize("planted,name", [
    ("rung_short", "live_3"), ("rung_short", "live_5"), ("rung_short", "equal_reach"),
    ("rung_short", "retired_scratch"), ("sorted", "interleaved"), ("sorted", "live_3"),
    ("sorted", "equal_reach")])
@pytest.mark.parametrize("case", ["gqa-f32-loop", "gqa-f32-switch", "latent-f32"])
def test_a_rung_one_row_short_and_an_unsort_that_sorts_are_caught(case, planted, name):
    """The controls: a ladder that holds one row fewer than are live leaves a
    live row unread just past a rung's edge (three live: the rung of 2; five:
    the loop's rung of 4, while the switch has no rung between 2 and 8 to
    take by mistake, and must come out right); rows handed back in the
    walk's order give a live row another row's attention (two live: the
    switch's rung too)."""
    lm, wrong = build(case, planted, fused=True, rows=ROWS)
    spec = pattern(lm, name)
    got = run_block(lm, wrong, *spec)
    lm, whole = build(case, "whole", fused=True, rows=ROWS)
    want = run_block(lm, whole, *spec)
    rows = np.nonzero(through(lm, *spec[:3]))[0]
    same = [close(mine, theirs, lm.config.dtype)
            for row in rows
            for mine, theirs in zip(pages_of(lm, got[1], row).values(),
                                    pages_of(lm, want[1], row).values())]
    live = int((spec[1] & ~spec[2]).sum())
    rungs = ladder(ROWS, loops(lm), chunk_of(lm))
    took = {"rung_short": next(r for r in rungs if r >= live - 1)}.get(planted, 0)
    harmless = took >= live or (planted == "sorted" and rungs[-2] < live)
    assert all(same) == harmless


@pytest.mark.parametrize("case", ["gqa-f32-loop", "gqa-bf16-switch", "mqa-int8-switch",
                                  "gqa-slab-loop", "latent-f32", "gqa-f32-loop-tp2"])
def test_no_row_live_of_eight_reads_one_row_once_and_counts_nothing(case):
    lm, bounded = build(case, "bounded", fused=True, rows=ROWS)
    lengths = pattern(lm, "interleaved")[0]
    toks, pools, read = run_block(lm, bounded, lengths, np.zeros((ROWS,), bool),
                                  np.zeros((ROWS,), bool))
    assert read.tolist() == [0, 0, 0] and (toks == 0).all()
    assert all(np.isfinite(p).all() for p in pools.values())


@pytest.mark.parametrize("rows,want,thin", [
    (1, (1,), (1,)), (2, (1, 2), (1, 2)), (3, (1, 2, 3), (1, 3)), (4, (1, 2, 4), (1, 4)),
    (6, (1, 2, 4, 6), (1, 6)), (8, (1, 2, 4, 8), (2, 8)), (16, (1, 2, 4, 8, 16), (4, 16))])
def test_the_ladder_follows_the_form(rows, want, thin):
    """The loop (chunks of 512 tokens or more, unless the caller says its
    cache cannot loop) holds a loop a rung: the powers of two up to the
    batch. The switch holds a body a (prefix, rung): a quarter of the batch,
    and the batch; over chunks as long as the loop's, the batch alone."""
    idx, live = jnp.zeros((rows,), jnp.int32), jnp.ones((rows,), bool)
    walk = KVWalk(4096, PAGE, idx, live)
    assert walk.loops and walk.rungs == want == tuple(ladder(rows, True))
    short = KVWalk(512, PAGE, idx, live)
    assert not short.loops and short.rungs == thin == tuple(ladder(rows, False))
    latent = KVWalk(4096, PAGE, idx, live, loops=False)
    assert not latent.loops and latent.rungs == (rows,) == tuple(ladder(rows, False, 512))
    assert KVWalk(512, PAGE, idx, live, loops=False).rungs == thin
    for walk in (walk, short, latent):
        for need in range(rows + 1):
            assert walk.rungs[int(walk.rung(need))] == next(r for r in walk.rungs if r >= need)
    # without ``live``, and over a table of one chunk: one rung
    assert KVWalk(512, PAGE, idx).rungs == KVWalk(4096, PAGE, idx).rungs == (rows,)
    assert KVWalk(128, PAGE, idx, live).rungs == (rows,)
    # the configuration's word is the walk's: the program's and the counter's
    for seq, rungs in ((4096, (rows,)), (512, thin)):
        cfg = DeepseekV2Config(**{**LATENT, "max_seq_len": seq, "page_size": PAGE})
        assert not llama.kv_walk(cfg, idx, live).loops
        assert llama.kv_walk(cfg, idx, live).rungs == rungs
    assert llama.kv_walk(LlamaConfig(**{**TINY, "max_seq_len": 4096, "page_size": PAGE}),
                         idx, live).rungs == want


@pytest.mark.parametrize("seed", range(6))
def test_the_order_is_a_stable_sort_by_reach_longest_first(seed):
    rng = np.random.RandomState(seed)
    idx = rng.choice([3, 130, 130, 700, 2000, 4090, 5000], ROWS)     # ties, and one past the end
    live = rng.rand(ROWS) < 0.6
    walk = KVWalk(4096, PAGE, jnp.asarray(idx, jnp.int32), jnp.asarray(live))
    reach = np.where(live, np.minimum(idx + 1, 4096), 0)
    order = np.argsort(-reach, kind="stable")
    assert np.asarray(walk.sorted_rows()[0]).tolist() == order.tolist()
    assert np.asarray(walk.sorted_rows()[1]).tolist() == np.argsort(order).tolist()
    x = jnp.arange(ROWS * 3).reshape(ROWS, 3)
    rows = walk.rows(x, jnp.zeros((ROWS, 4096 // PAGE), jnp.int32), 0)
    for r in walk.rungs[:-1]:           # what top(r) picks, back() returns to its rows
        top = rows.top(r)
        assert np.asarray(top.q).tolist() == np.asarray(x)[order[:r]].tolist()
        assert np.asarray(top.idx).tolist() == idx[order[:r]].tolist()
        put = np.asarray(top.back(top.q))
        assert np.array_equal(put[order[:r]], np.asarray(x)[order[:r]])
        assert (put[order[r:]] == 0).all()
    assert rows.top(ROWS) is rows and rows.back(x) is x
    assert int(walk.live_rows) == live.sum()
    assert (reach[order[:live.sum()]] > 0).all()         # the live rows are a prefix of the order
    assert int(walk.row_slots) == ladder(ROWS, True)[int(walk.rung(live.sum()))] * int(walk.tokens)
    assert int(walk.row_slots) <= ROWS * int(walk.tokens)


@pytest.mark.parametrize("seq,page,want", [
    (4096, 16, (512, 8)), (1024, 16, (128, 8)), (512, 16, (128, 4)), (128, 16, (128, 1)),
    (64, 8, (64, 1)), (4096, 0, (512, 8)), (2048, 128, (256, 8)), (768, 16, (128, 6)),
    (1536, 16, (192, 8)), (32768, 16, (4096, 8))])
def test_the_chunk_rule(seq, page, want):
    """An eighth of the table, not under 128 tokens, whole pages, a divisor."""
    pages, chunk, n_chunks = KVWalk.cut(seq, page)
    assert (chunk, n_chunks) == want and pages * (page or 1) == chunk
    assert chunk * n_chunks == seq and chunk % (page or 1) == 0
    walk = KVWalk(seq, page, jnp.zeros((2,), jnp.int32))
    assert (walk.pages, walk.chunk, walk.n_chunks) == (pages, chunk, n_chunks)
    assert walk.loops == (chunk >= 512)
    assert not KVWalk(seq, page, jnp.zeros((2,), jnp.int32), loops=False).loops


def test_a_model_called_without_live_counts_every_row():
    """``live=None`` (plain ``generate``, the stand-alone step): too wide at
    worst, and every row of the batch. The stand-alone step of a state whose
    longest row is retired reads as far as that row."""
    idx = jnp.asarray([10, 3000, 7])
    every = KVWalk(4096, 16, idx)
    assert int(every.turns) == 6 and every.rungs == (3,)
    assert int(every.row_slots) == 3 * int(every.tokens) == 3 * 3072
    some = KVWalk(4096, 16, idx, jnp.asarray([True, False, True]))
    assert int(some.turns) == 1 and some.rungs == (1, 2, 3)
    assert int(some.live_rows) == 2 and int(some.row_slots) == 2 * 512
    assert int(KVWalk(4096, 16, idx, jnp.asarray([[True], [True], [False]])).tokens) == 3072
    assert int(KVWalk(4096, 16, jnp.asarray([5000, 1])).turns) == 8       # a stale index past the end


def test_the_fused_block_and_generate_agree_where_every_row_counts():
    """The fused block of a model whose OTHER callers give no ``live`` still
    runs: ``lm.generate`` (no ``live`` anywhere) and the engine's fused
    blocks give the same greedy stream past a chunk edge."""
    from neuronx_distributed_tpu.inference import ServeEngine

    psm.destroy_model_parallel()
    cfg = LlamaConfig(**{**TINY, "max_seq_len": 512})
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=2)
    prompt = np.random.RandomState(3).randint(1, 127, (1, 120)).astype(np.int32)
    slab = CausalLM(cfg, params, LlamaForCausalLM, buckets=(128,), max_batch=1)
    want = slab.generate(prompt, 16).tokens[0]
    paged = CausalLM(cfg, params, LlamaForCausalLM, buckets=(128,), max_batch=2, page_size=PAGE)
    engine = ServeEngine(paged, block_steps=K)
    engine.submit(prompt[0], max_new_tokens=16)
    while engine.step_block():
        pass
    assert list(engine.completed[0].tokens) == want.tolist()
    # 15 decode steps from 120 tokens: reaches 121 .. 135, the edge at 128
    assert engine.stats["kv_walk_steps"] == 16
    assert engine.stats["kv_walk_tokens"] == walked(paged, [121 + i for i in range(16)])
    # one live row of two: the rung of 1 (a quarter of two rows is one), so a
    # row's slots and no more
    assert engine.stats["kv_walk_row_slots"] == engine.stats["kv_walk_tokens"]


def test_dataclass_configs_gain_no_field():
    """No new option: the walk is derived from ``max_seq_len`` and
    ``page_size`` alone (and what a configuration's cache IS:
    ``DeepseekV2Config.kv_walk_loops`` is a property, not a field)."""
    for config in (LlamaConfig, DeepseekV2Config):
        names = {f.name for f in dataclasses.fields(config)}
        assert not {n for n in names if "walk" in n or "chunk_tokens" in n or "extent" in n
                    or "rung" in n or "ladder" in n}
    assert not hasattr(llama, "_WALK_FORM")
