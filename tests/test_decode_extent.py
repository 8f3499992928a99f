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

The one bug the change can have is a bound too SHORT for a live row, and the
control plants it: the same comparison with the bound one chunk short must
fail wherever a step reads more than one chunk (at ``chunk - 1`` one chunk is
all the right bound reads, and the least any step reads).

The ROW bound (the second half of the file) has two more: a rung of rows one
row short of the live ones, and rows handed back in the walk's order instead
of the step's. Both are planted too. Beside the oracle stands the parent's
read ("every_row": every row of the batch inside the bound, no order, one
rung), and what a live row computes and writes must EQUAL it, bit for bit:
the rows of a batch do not meet in the attention, and a chunk a row is not
read at held no visible key for it.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import CausalLM, causal_lm
from neuronx_distributed_tpu.inference.sampling import SlotSampler
from neuronx_distributed_tpu.models import llama
from neuronx_distributed_tpu.models.deepseek_v2 import (
    LATENT_LEAF,
    DeepseekV2Attention,
    DeepseekV2Config,
    DeepseekV2ForCausalLM,
)
from neuronx_distributed_tpu.models.llama import (
    KVWalk,
    LlamaAttention,
    LlamaConfig,
    LlamaForCausalLM,
    cached_attention,
)
from neuronx_distributed_tpu.parallel import mesh as psm
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)

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

_BUILT = {}


def build(case, how, fused=False, rows=B):
    """``(lm, decode or fused program)`` of ``case`` with ``rows`` slots reading
    the cache ``how``, built once a module; the world (mesh) is the case's,
    made anew for the test (``conftest.py`` takes it down after each)."""
    kind, over, lm_kw, tp, seq = CASES[case]
    psm.destroy_model_parallel()
    psm.initialize_model_parallel(tensor_model_parallel_size=tp,
                                  devices=jax.devices()[:tp])
    if (case, how, fused, rows) not in _BUILT:
        _BUILT[case, how, fused, rows] = _build(case, how, fused, rows)
    return _BUILT[case, how, fused, rows]


def _build(case, how, fused, rows):
    kind, over, lm_kw, tp, seq = CASES[case]
    if kind == "latent":
        cfg, cls = DeepseekV2Config(**{**LATENT, **over, "max_seq_len": seq}), DeepseekV2ForCausalLM
    else:
        cfg, cls = LlamaConfig(**{**TINY, **over, "max_seq_len": seq}), LlamaForCausalLM
    nxd = neuronx_distributed_config(tensor_parallel_size=tp)
    params = initialize_parallel_model(nxd, lambda: cls(cfg), jnp.zeros((1, 8), jnp.int32)).params
    page = lm_kw.get("page_size", PAGE)
    pages = dict(page_size=page, page_pool_pages=rows * seq // page + 1) if page else {}
    kw = {k: v for k, v in lm_kw.items() if k != "page_size"}
    lm = CausalLM(cfg, params, cls, buckets=(16,), max_batch=rows, **pages, **kw)
    with reads(how):
        program = (lm.compile_session_decode_fused(K, SlotSampler(), 0) if fused
                   else lm.compile()._decode)
    return lm, program


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


# ------------------------------------------------- the row bound (ISSUE 40)

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


def pages_of(lm, pools, row):
    """What ``row`` holds of every paged pool leaf (scales apart)."""
    per_row = lm.config.max_seq_len // PAGE
    first = 1 + row * per_row
    return {name: pool[:, first:first + per_row] for name, pool in pools.items()
            if not name.endswith("_scale']")}


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
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(2), jnp.zeros((1, 8), jnp.int32)))["params"]
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
