"""A one-token decode step reads the cache as far as its longest LIVE row
reaches (ISSUE 38) and of its live rows only (ISSUE 40): ``models/llama.py::KVWalk``.

The oracle is kept HERE: gather all of ``max_seq_len`` through the block table
and attend over it behind the mask (``cached_attention``; for the latent
cache the same two einsums over the whole slab). It takes the place of each
attention's ``_walk_attention`` in a second ``CausalLM``, so both sides run the
same model, weights and programs but for how far the cache is read. The state
is made by hand: pools of random values (garbage past every row's length, as
reused pages hold), block tables, lengths. Logits of the stand-alone step
within float32 reassociation (the loop's running softmax adds in another
order; the switch adds the same numbers, zeros left out), greedy streams of a
fused block equal, and the K/V the block wrote (what every later layer saw of
the attention) as close as the logits.

That oracle shares the program's gather by whole pages and its
``cached_attention``. An INDEPENDENT one stands beside it for the read alone
(``test_the_walk_reads_what_the_page_wise_reference_reads``):
``inference/kv_quant.py::reference_paged_attention``, which finds every slot
through the block table one by one, on pools and tables made here (pages
shuffled through the pool, as an allocator leaves them).

The one bug the change can have is a bound too SHORT for a live row, and the
control plants it: the same comparison with the bound one chunk short must
fail wherever a step reads more than one chunk (at ``chunk - 1`` one chunk is
all the right bound reads, and the least any step reads).

The ROW bound (``tests/test_decode_rows.py``, on this file's oracle, builders
and Python models of the counters; a file of its own so that a second worker
runs it) has two more: a rung of rows one row short of the live ones, and rows
handed back in the walk's order instead of the step's. Both are planted too. Beside the oracle stands the parent's
read ("every_row": every row of the batch inside the bound, no order, one
rung), and what a live row computes and writes must EQUAL it, bit for bit:
the rows of a batch do not meet in the attention, and a chunk a row is not
read at held no visible key for it.
"""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, causal_lm
from neuronx_distributed_tpu.inference.kv_quant import reference_paged_attention
from neuronx_distributed_tpu.inference.sampling import SlotSampler
from neuronx_distributed_tpu.models import llama
from neuronx_distributed_tpu.models.deepseek_v2 import (
    LATENT_LEAF,
    DeepseekV2Attention,
    DeepseekV2Config,
    DeepseekV2ForCausalLM,
)
from neuronx_distributed_tpu.models.llama import (
    KVLayerView,
    KVWalk,
    LlamaAttention,
    LlamaConfig,
    LlamaForCausalLM,
    cached_attention,
)
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)
from tests import tiny

B, K, PAGE = 4, 4, 16
TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, dtype=jnp.float32, param_dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
LATENT = dict(vocab_size=128, hidden_size=32, intermediate_size=48, num_layers=3, num_heads=4,
              num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, first_k_dense=1, moe_intermediate_size=16,
              n_shared_experts=1, num_experts=4, n_group=2, topk_group=1, top_k=2,
              dtype=jnp.float32, param_dtype=jnp.float32, use_flash_attention=False,
              remat_policy=None, moe_mode="capacity_factor")
BF16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
MHA_QK, MQA = dict(num_kv_heads=4, qk_norm=True), dict(num_kv_heads=1)

# name: (model, config overrides, CausalLM keywords, TP degree, max_seq_len).
# 4 096 slots are 8 chunks of 512, read by the loop; 512 slots are 4 chunks of
# 128, read by the switch (KVWalk.loops); the latent cache always by the switch
CASES = {
    "gqa-f32-loop": ("llama", {}, {}, 1, 4096),
    "gqa-f32-switch": ("llama", {}, {}, 1, 512),
    "mha_qknorm-f32-loop": ("llama", MHA_QK, {}, 1, 4096),
    "mha_qknorm-f32-switch": ("llama", MHA_QK, {}, 1, 512),
    "mqa-f32-loop": ("llama", MQA, {}, 1, 4096),
    "gqa-bf16-loop": ("llama", BF16, {}, 1, 4096),
    "gqa-bf16-switch": ("llama", BF16, {}, 1, 512),
    "gqa-int8-loop": ("llama", {}, dict(page_dtype="int8"), 1, 4096),
    "mqa-int8-switch": ("llama", MQA, dict(page_dtype="int8"), 1, 512),
    "gqa-slab-loop": ("llama", {}, dict(page_size=0), 1, 4096),
    "gqa-f32-loop-tp2": ("llama", {}, {}, 2, 4096),
    "gqa-int8-switch-tp2": ("llama", {}, dict(page_dtype="int8"), 2, 512),
    "latent-f32": ("latent", {}, {}, 1, 512),
    "latent-f32-long": ("latent", {}, {}, 1, 4096),
    "latent-bf16": ("latent", BF16, {}, 1, 512),
    "latent-slab": ("latent", {}, dict(page_size=0), 1, 512),
}
# the longest live row holds this many tokens before the step
LENGTHS = {"chunk-1": lambda c, s: c - 1, "chunk": lambda c, s: c,
           "chunk+1": lambda c, s: c + 1, "max-1": lambda c, s: s - 1}


# ------------------------------------------------------------ the oracle

def whole_gqa(self, q, kv, walk, table):
    """All of ``max_seq_len`` through the table, then ``cached_attention``."""
    cfg, b = self.config, q.shape[0]

    def whole(name):
        flat = kv.flat(name)
        if table is None:
            return jax.lax.dynamic_slice_in_dim(flat, kv.first_row(b), b)
        pages = flat[table]
        if cfg.page_dtype == "int8":
            pages = (pages.astype(jnp.float32) * kv.flat(name + "_scale")[table]).astype(cfg.dtype)
        return pages.reshape(b, cfg.max_seq_len, *pages.shape[-2:])

    return cached_attention(q, whole("cached_key"), whole("cached_value"), walk.idx)


def whole_latent(self, q_all, kv, walk, table):
    cfg, b = self.config, q_all.shape[0]
    pool = kv.flat(LATENT_LEAF)
    slab = (pool[table] if table is not None
            else jax.lax.dynamic_slice_in_dim(pool, kv.first_row(b), b))
    slab = slab.reshape(b, cfg.max_seq_len, cfg.latent_dim)
    exact = dict(preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)
    scores = jnp.einsum("bsnc,bjc->bnsj", q_all, slab, **exact) * cfg.softmax_scale
    visible = jnp.arange(cfg.max_seq_len)[None, :] <= walk.idx[:, None]
    probs = jax.nn.softmax(jnp.where(visible[:, None, None], scores, -1e30), axis=-1)
    return jnp.einsum("bnsj,bjc->bsnc", probs, slab, **exact)[..., :cfg.kv_lora_rank]


@contextlib.contextmanager
def reads(how):
    """Programs built inside read the cache ``how``: "bounded" (the program's
    own), "whole" (the oracle), "short" (the planted bug: one chunk less),
    "every_row" (the parent's read: no row bound), "rung_short" (planted: the
    rung chosen for one row fewer) or "sorted" (planted: rows put back by the
    order, the sort once more, and not by their places)."""
    with pytest.MonkeyPatch.context() as patch:
        init = KVWalk.__init__
        if how == "every_row":
            def every_row(self, *args, **kwargs):
                init(self, *args, **kwargs)
                self.rungs = (self.idx.shape[0],)

            patch.setattr(KVWalk, "__init__", every_row)
        elif how == "rung_short":
            rung = KVWalk.rung
            patch.setattr(KVWalk, "rung", lambda self, rows: rung(self, rows - 1))
        elif how == "sorted":
            sorted_rows = KVWalk.sorted_rows
            patch.setattr(KVWalk, "sorted_rows", lambda self: (sorted_rows(self)[0],) * 2)
        if how == "whole":
            patch.setattr(LlamaAttention, "_walk_attention", whole_gqa)
            patch.setattr(DeepseekV2Attention, "_walk_attention", whole_latent)
        elif how == "short":
            def short(self, *args, **kwargs):
                init(self, *args, **kwargs)
                self.turns = self.turns - 1

            patch.setattr(KVWalk, "__init__", short)
        yield


# ----------------------------------------------------- models and states

def build(case, how, fused=False, rows=B):
    """``(lm, decode or fused program)`` of ``case`` with ``rows`` slots reading
    the cache ``how``, built once a process and the case's weights once for
    all of them; the world (mesh) is the case's, made anew for the test
    (``conftest.py`` takes it down after each)."""
    kind, over, lm_kw, tp, seq = CASES[case]
    tiny.world(tp)
    if kind == "latent":
        cfg, cls = DeepseekV2Config(**{**LATENT, **over, "max_seq_len": seq}), DeepseekV2ForCausalLM
    else:
        cfg, cls = LlamaConfig(**{**TINY, **over, "max_seq_len": seq}), LlamaForCausalLM
    params = tiny.built(("decode_extent", case), lambda: initialize_parallel_model(
        neuronx_distributed_config(tensor_parallel_size=tp), lambda: cls(cfg),
        jnp.zeros((1, 8), jnp.int32)).params)

    def make():
        page = lm_kw.get("page_size", PAGE)
        pages = dict(page_size=page, page_pool_pages=rows * seq // page + 1) if page else {}
        kw = {k: v for k, v in lm_kw.items() if k != "page_size"}
        lm = CausalLM(cfg, params, cls, buckets=(16,), max_batch=rows, **pages, **kw)
        with reads(how):
            return lm, (lm.compile_session_decode_fused(K, SlotSampler(), 0) if fused
                        else lm.compile()._decode)

    return tiny.built(("decode_extent", case, how, fused, rows), make)


def state(lm, lengths, mapped=None, seed=0):
    """A session cache holding ``lengths`` tokens a row: random pools (int8
    pages with random scales), row r's table at pages 1 + r * pages.., rows
    not ``mapped`` at scratch (page 0) as ``CausalLM.retire`` leaves them."""
    rng = np.random.RandomState(seed)
    cfg = lm.config
    session = lm.start_session()
    rows = lm.max_batch
    mapped = np.ones((rows,), bool) if mapped is None else np.asarray(mapped)

    def fill(path, leaf):
        name = jax.tree_util.keystr(path)
        if "cached_" not in name:
            return leaf
        if name.endswith("_scale']"):
            value = rng.uniform(0.002, 0.02, leaf.shape)
        elif leaf.dtype == jnp.int8:
            value = rng.randint(-127, 128, leaf.shape)
        else:
            value = rng.standard_normal(leaf.shape) * 0.7
        return jax.device_put(jnp.asarray(value, leaf.dtype), leaf.sharding)

    cache = jax.tree_util.tree_map_with_path(fill, session.cache)
    if lm.paged:
        per_row = cfg.max_seq_len // cfg.page_size
        tables = 1 + np.arange(rows)[:, None] * per_row + np.arange(per_row)[None, :]
        cache = causal_lm._set_block_tables(cache, np.where(mapped[:, None], tables, 0))
    return causal_lm._set_cache_index(cache, jnp.asarray(lengths, jnp.int32))


def chunk_of(lm):
    return KVWalk(lm.config.max_seq_len, lm.config.page_size, jnp.zeros((B,), jnp.int32)).chunk


def row_lengths(lm, which):
    """The longest row where ``which`` says, the others shorter."""
    chunk, seq = chunk_of(lm), lm.config.max_seq_len
    longest = LENGTHS[which](chunk, seq)
    return np.asarray([longest, longest // 2, 7, 1], np.int32)


def step_logits(lm, program, lengths):
    tok = jnp.asarray(np.arange(1, B + 1)[:, None], jnp.int32)
    logits, _ = program(lm.params, state(lm, lengths), tok)
    return np.asarray(logits[:, 0], np.float32)


def close(got, want, dtype):
    """Float32: 2e-6, the reassociated sum. bfloat16: one ulp of the value
    (2 ** -7 of it at most), and of 1 below that."""
    if dtype == jnp.bfloat16:
        return bool((np.abs(got - want) <= 2.0 ** -7 * np.maximum(np.abs(want), 1.0)).all())
    return bool(np.abs(got - want).max() <= 2e-6)


def run_block(lm, fused, lengths, active, done, mapped=None):
    """One fused block of ``K`` greedy steps from the hand-made state."""
    rows = lm.max_batch
    outs = fused(lm.params, state(lm, lengths, mapped), jnp.ones((rows, 1), jnp.int32),
                 jax.random.split(jax.random.key(1), rows), jnp.asarray(done),
                 lm.block_rows(np.zeros((rows,), np.int32), lengths, active,
                               np.full((rows,), -1), np.ones((rows,)), np.ones((rows,), bool)))
    pools = {jax.tree_util.keystr(p): np.asarray(leaf, np.float32) for p, leaf in
             jax.tree_util.tree_flatten_with_path(outs[1])[0]
             if "cached_" in jax.tree_util.keystr(p)}
    return np.asarray(outs[0]), pools, np.asarray(outs[5])


def walked(lm, reaches):
    """Python model of ``kv_walk_tokens``: a step whose longest live row
    reaches ``r`` slots reads whole chunks up to it."""
    chunk = chunk_of(lm)
    return sum(-(-min(r, lm.config.max_seq_len) // chunk) * chunk for r in reaches)


def ladder(rows, loops, chunk=128):
    """The rungs of ``rows`` slots: the powers of two below them where the
    step reads by the loop; by the switch one rung of a quarter of them, and
    none where its chunks are long; then the rows themselves."""
    if loops:
        below = [r for r in (1, 2, 4, 8, 16, 32) if r < rows]
    else:
        below = [max(rows // 4, 1)] if chunk < 512 else []
    return [r for r in below if r < rows] + [rows]


def loops(lm):
    """By the loop: a GQA cache whose chunks hold 512 tokens or more."""
    return chunk_of(lm) >= 512 and not isinstance(lm.config, DeepseekV2Config)


def live_steps(lm, lengths, active, done):
    """The block's bookkeeping in Python: the reaches of the rows live at each
    of its ``K`` steps (a row is done once its next token would not fit)."""
    lengths, done, seq = np.array(lengths), np.array(done), lm.config.max_seq_len
    steps = []
    for _ in range(K):
        steps.append((lengths[np.asarray(active) & ~done] + 1).tolist())
        lengths = lengths + 1
        done = done | (np.asarray(active) & (lengths + 1 >= seq))
    return steps


def sums(lm, steps):
    """Python model of the three sums a block returns (``_walk_sums``) from
    the live rows' reaches step by step: slots read of the longest row, steps
    with a live row, slots read over the rows of the rung that holds them."""
    chunk, seq = chunk_of(lm), lm.config.max_seq_len
    rungs = ladder(lm.max_batch, loops(lm), chunk)
    tokens = count = row_slots = 0
    for reaches in (r for r in steps if r):
        reaches = [min(r, seq) for r in reaches]
        turns = -(-max(reaches) // chunk)
        tokens, count = tokens + turns * chunk, count + 1
        row_slots += next(r for r in rungs if r >= len(reaches)) * turns * chunk
    return [tokens, count, row_slots]


def pages_of(lm, pools, row):
    """What ``row`` holds of every paged pool leaf (scales apart)."""
    per_row = lm.config.max_seq_len // PAGE
    first = 1 + row * per_row
    return {name: pool[:, first:first + per_row] for name, pool in pools.items()
            if not name.endswith("_scale']")}


# ----------------------------------------------------------------- tests

@pytest.mark.parametrize("which", list(LENGTHS))
@pytest.mark.parametrize("case", list(CASES))
def test_the_bounded_step_gives_the_whole_reads_logits(case, which):
    lm, bounded = build(case, "bounded")
    lengths = row_lengths(lm, which)
    got = step_logits(lm, bounded, lengths)
    lm, whole = build(case, "whole")
    want = step_logits(lm, whole, lengths)
    assert np.isfinite(got).all()
    assert close(got, want, lm.config.dtype), np.abs(got - want).max()
    assert (got.argmax(-1) == want.argmax(-1)).all()


@pytest.mark.parametrize("which", ["chunk", "chunk+1", "max-1"])
@pytest.mark.parametrize("case", ["gqa-f32-loop", "gqa-f32-switch", "gqa-int8-loop",
                                  "latent-f32", "gqa-f32-loop-tp2"])
def test_a_bound_one_chunk_short_is_caught(case, which):
    """The control: the comparison above, the bound computed one chunk short."""
    lm, short = build(case, "short")
    lengths = row_lengths(lm, which)
    got = step_logits(lm, short, lengths)
    lm, whole = build(case, "whole")
    want = step_logits(lm, whole, lengths)
    assert not close(got[:1], want[:1], lm.config.dtype)


def test_at_chunk_minus_one_a_step_reads_one_chunk_and_no_less():
    """Why the control leaves ``chunk - 1`` out: the right bound is one chunk
    there, and a walk never reads less (every row sees its slot 0)."""
    walk = KVWalk(512, PAGE, jnp.asarray([126, 3]))
    assert (walk.chunk, walk.n_chunks, int(walk.turns)) == (128, 4, 1)
    assert int(KVWalk(512, PAGE, jnp.asarray([0, 0]), jnp.zeros((2,), bool)).turns) == 1


@pytest.mark.parametrize("beside", ["retired", "done", "every_row"])
@pytest.mark.parametrize("case", ["gqa-f32-loop", "gqa-bf16-switch", "mqa-int8-switch",
                                  "latent-f32", "gqa-f32-loop-tp2"])
def test_only_live_rows_set_the_bound(case, beside):
    """One live row of 200 tokens beside a retired slot (a long stale
    ``cache_index`` over scratch) or a done row (long, still mapped): the live
    row's stream and the K/V it wrote are the whole read's, and the block
    read as far as the LIVE row reaches. ``every_row``: all rows live, the
    long one sets the bound."""
    lm, bounded = build(case, "bounded", fused=True)
    seq = lm.config.max_seq_len
    lengths = np.asarray([200, seq - 60, 5, 9], np.int32)
    active = np.asarray([True, beside != "retired", beside == "every_row", False])
    done = np.asarray([False, beside == "done", False, False])
    mapped = np.asarray([True, beside != "retired", True, True])
    got = run_block(lm, bounded, lengths, active, done, mapped)
    lm, whole = build(case, "whole", fused=True)
    want = run_block(lm, whole, lengths, active, done, mapped)
    live = active & ~done
    assert (got[0][:, live] == want[0][:, live]).all()
    per_row = seq // PAGE
    for name, pool in got[1].items():
        if name.endswith("_scale']") or not lm.paged:
            continue
        for row in np.nonzero(live)[0]:      # the pages the live rows wrote into
            first = 1 + row * per_row
            mine, theirs = (p[:, first:first + per_row] for p in (pool, want[1][name]))
            if lm.config.page_dtype == "int8":
                assert np.abs(mine - theirs).max() <= 1, name     # one quantisation step
            else:
                assert close(mine, theirs, lm.config.dtype), name
    longest = (seq - 60 if beside == "every_row" else 200)
    assert got[2].tolist() == sums(lm, live_steps(lm, lengths, active, done))
    assert got[2][:2].tolist() == [walked(lm, [longest + 1 + i for i in range(K)]), K]
    assert want[2].tolist() == got[2].tolist()      # the counter is the bound's, not the read's


@pytest.mark.parametrize("case", ["gqa-f32-loop", "gqa-f32-switch", "latent-f32"])
def test_a_row_crosses_a_chunk_edge_inside_a_block(case):
    lm, bounded = build(case, "bounded", fused=True)
    chunk = chunk_of(lm)
    lengths = np.asarray([chunk - 2, 40, 3, 3], np.int32)
    active, done = np.asarray([True, True, False, False]), np.zeros((B,), bool)
    got = run_block(lm, bounded, lengths, active, done)
    lm, whole = build(case, "whole", fused=True)
    want = run_block(lm, whole, lengths, active, done)
    assert (got[0][:, :2] == want[0][:, :2]).all()
    for row in (0, 1):      # what the two live rows wrote (a row that is not live computes zeros)
        mine, theirs = pages_of(lm, got[1], row), pages_of(lm, want[1], row)
        for name in mine:
            assert close(mine[name], theirs[name], lm.config.dtype), name
    # reaches chunk - 1, chunk (one chunk each), chunk + 1, chunk + 2 (two)
    assert got[2][:2].tolist() == [6 * chunk, K] == [walked(lm, [chunk - 1 + i for i in range(K)]), K]
    # two live rows of four over the same chunks: the loop's rung of 2, the switch's top rung
    assert got[2].tolist() == sums(lm, live_steps(lm, lengths, active, done))
    assert got[2][2] == (2 if loops(lm) else B) * got[2][0]


def test_no_live_row_reads_one_chunk_counts_nothing_and_stays_finite():
    lm, bounded = build("gqa-f32-loop", "bounded", fused=True)
    lengths = np.asarray([900, 700, 5, 9], np.int32)
    toks, pools, read = run_block(lm, bounded, lengths, np.zeros((B,), bool), np.zeros((B,), bool))
    assert read.tolist() == [0, 0, 0] and (toks == 0).all()
    assert all(np.isfinite(p).all() for p in pools.values())


# ------------------------------- the read against the page-wise reference

HEADS = {"gqa": 2, "mha": 4, "mqa": 1}          # KV heads under four query heads
PAGES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.float32}   # q's dtype
FORMS = {"loop": 4096, "switch": 512}           # max_seq_len: chunks of 512 / of 128
HEAD = 8


def ragged(seq, chunk):
    """``(lengths, live)`` of four rows. A dead row with the longest index
    (nobody reads it: the two live rows take the rung of two), all live (the
    top rung: nothing sorted), and lengths at a page's and a chunk's edges."""
    return {
        "ragged_dead_row": ([0, seq - 2, chunk + 7, 2 * chunk - 1], [True, False, True, False]),
        "all_live": ([chunk - 1, 3, 2 * chunk, chunk + PAGE], [True] * 4),
        "stale_bytes": ([PAGE - 1, chunk, 5, chunk + 1], [True, True, False, True]),
    }


@functools.lru_cache(maxsize=None)
def walk_read(n_kv, pages, seq):
    """``_walk_attention`` of one layer that owns its pool, jitted once a shape."""
    dtype = PAGES[pages]
    cfg = dataclasses.replace(
        LlamaConfig(**{**TINY, "num_kv_heads": n_kv, "head_dim": HEAD, "max_seq_len": seq,
                       "dtype": dtype, "param_dtype": dtype}),
        decode=True, page_size=PAGE, page_pool_pages=B * seq // PAGE + 1,
        page_dtype="int8" if pages == "int8" else None)

    @jax.jit
    def read(q, leaves, table, idx, live):
        view = KVLayerView(jnp.int32(0), {n: leaf[None] for n, leaf in leaves.items()})
        return LlamaAttention(cfg)._walk_attention(q, view, llama.kv_walk(cfg, idx, live), table)

    return cfg, read


@pytest.mark.parametrize("rows", ["ragged_dead_row", "all_live", "stale_bytes"])
@pytest.mark.parametrize("pages", list(PAGES))
@pytest.mark.parametrize("heads", list(HEADS))
def test_the_walk_reads_what_the_page_wise_reference_reads(heads, pages, rows):
    """One new token a row through ``KVWalk``, by the loop AND by the switch,
    against ``reference_paged_attention`` on the same pools: every live row's
    output to float32 reassociation (one bf16 step for a bf16 model). int8
    pages: both sides widen the SAME quantised values by the same scales.
    ``stale_bytes``: what lies past a row's length (the rest of its last page,
    the pages after it, the scratch page) holds huge values on the walk's side
    and not on the reference's: behind the mask they weigh exactly nothing."""
    n_kv, dtype = HEADS[heads], PAGES[pages]
    for form, seq in FORMS.items():
        cfg, read = walk_read(n_kv, pages, seq)
        walk = llama.kv_walk(cfg, jnp.zeros((B,), jnp.int32))
        assert (walk.loops, walk.n_chunks > 1) == (form == "loop", True)
        lengths, live = (np.asarray(x) for x in ragged(seq, walk.chunk)[rows])
        rng = np.random.RandomState(n_kv * 100 + seq)
        npages, per_row = cfg.page_pool_pages, seq // PAGE
        shape = (npages, PAGE, n_kv, HEAD)
        if pages == "int8":
            leaves = {n: jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
                      for n in ("cached_key", "cached_value")}
            leaves.update({n + "_scale": jnp.asarray(rng.uniform(0.002, 0.02, (npages, 1, n_kv, 1)),
                                                     jnp.float32) for n in list(leaves)})
        else:
            leaves = {n: jnp.asarray(rng.standard_normal(shape) * 0.7, dtype)
                      for n in ("cached_key", "cached_value")}
        # every row's pages scattered over the pool; page 0 is nobody's (scratch)
        table = jnp.asarray(1 + rng.permutation(npages - 1)[:B * per_row].reshape(B, per_row),
                            jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, 1, 4, HEAD)), dtype)
        scales = (dict(k_scale=leaves["cached_key_scale"], v_scale=leaves["cached_value_scale"])
                  if pages == "int8" else {})
        want = np.asarray(reference_paged_attention(
            q, leaves["cached_key"], leaves["cached_value"], table, jnp.asarray(lengths),
            **scales), np.float32)
        if rows == "stale_bytes":
            slot = np.arange(per_row * PAGE)
            past = np.ones((npages, PAGE), bool)
            for r in range(B):
                held = slot <= lengths[r]
                past[np.asarray(table)[r, slot[held] // PAGE], slot[held] % PAGE] = False
            big = 120 if pages == "int8" else 1e4
            leaves = {n: leaf if n.endswith("_scale") else
                      jnp.where(past[:, :, None, None], jnp.asarray(big, leaf.dtype), leaf)
                      for n, leaf in leaves.items()}
        got = np.asarray(read(q, leaves, table, jnp.asarray(lengths), jnp.asarray(live)),
                         np.float32)
        assert np.isfinite(got).all(), form
        assert close(got[live], want[live], dtype), (form, np.abs(got - want)[live].max())
        rung = next(r for r in ladder(B, walk.loops, walk.chunk) if r >= live.sum())
        if rung < B:            # a row outside its rung computes zeros, nothing else
            assert (got[~live] == 0).all(), form
