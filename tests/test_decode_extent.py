"""A one-token decode step reads the cache as far as its longest LIVE row
reaches (ISSUE 38, ``models/llama.py::KVWalk``).

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
    own), "whole" (the oracle) or "short" (the planted bug: one chunk less)."""
    with pytest.MonkeyPatch.context() as patch:
        if how == "whole":
            patch.setattr(LlamaAttention, "_walk_attention", whole_gqa)
            patch.setattr(DeepseekV2Attention, "_walk_attention", whole_latent)
        elif how == "short":
            init = KVWalk.__init__

            def short(self, *args, **kwargs):
                init(self, *args, **kwargs)
                self.turns = self.turns - 1

            patch.setattr(KVWalk, "__init__", short)
        yield


# ----------------------------------------------------- models and states

_BUILT = {}


def build(case, how, fused=False):
    """``(lm, decode or fused program)`` of ``case`` reading the cache ``how``,
    built once a module; the world (mesh) is the case's, made anew for the
    test (``conftest.py`` takes it down after each)."""
    kind, over, lm_kw, tp, seq = CASES[case]
    psm.destroy_model_parallel()
    psm.initialize_model_parallel(tensor_model_parallel_size=tp,
                                  devices=jax.devices()[:tp])
    if (case, how, fused) not in _BUILT:
        _BUILT[case, how, fused] = _build(case, how, fused)
    return _BUILT[case, how, fused]


def _build(case, how, fused):
    kind, over, lm_kw, tp, seq = CASES[case]
    if kind == "latent":
        cfg, cls = DeepseekV2Config(**{**LATENT, **over, "max_seq_len": seq}), DeepseekV2ForCausalLM
    else:
        cfg, cls = LlamaConfig(**{**TINY, **over, "max_seq_len": seq}), LlamaForCausalLM
    nxd = neuronx_distributed_config(tensor_parallel_size=tp)
    params = initialize_parallel_model(nxd, lambda: cls(cfg), jnp.zeros((1, 8), jnp.int32)).params
    page = lm_kw.get("page_size", PAGE)
    pages = dict(page_size=page, page_pool_pages=B * seq // page + 1) if page else {}
    kw = {k: v for k, v in lm_kw.items() if k != "page_size"}
    lm = CausalLM(cfg, params, cls, buckets=(16,), max_batch=B, **pages, **kw)
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
    mapped = np.ones((B,), bool) if mapped is None else np.asarray(mapped)

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
        tables = 1 + np.arange(B)[:, None] * per_row + np.arange(per_row)[None, :]
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
    outs = fused(lm.params, state(lm, lengths, mapped), jnp.ones((B, 1), jnp.int32),
                 jax.random.split(jax.random.key(1), B), jnp.zeros((B,), jnp.int32),
                 jnp.asarray(lengths, jnp.int32), jnp.asarray(active), jnp.asarray(done),
                 jnp.full((B,), -1, jnp.int32), jnp.ones((B,), jnp.float32),
                 jnp.ones((B,), bool))
    pools = {jax.tree_util.keystr(p): np.asarray(leaf, np.float32) for p, leaf in
             jax.tree_util.tree_flatten_with_path(outs[1])[0]
             if "cached_" in jax.tree_util.keystr(p)}
    return np.asarray(outs[0]), pools, np.asarray(outs[5])


def walked(lm, reaches):
    """Python model of ``kv_walk_tokens``: a step whose longest live row
    reaches ``r`` slots reads whole chunks up to it."""
    chunk = chunk_of(lm)
    return sum(-(-min(r, lm.config.max_seq_len) // chunk) * chunk for r in reaches)


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
    assert got[2].tolist() == [walked(lm, [longest + 1 + i for i in range(K)]), K]
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
    for name, pool in got[1].items():
        assert close(pool, want[1][name], lm.config.dtype), name
    # reaches chunk - 1, chunk (one chunk each), chunk + 1, chunk + 2 (two)
    assert got[2].tolist() == [6 * chunk, K] == [walked(lm, [chunk - 1 + i for i in range(K)]), K]


def test_no_live_row_reads_one_chunk_counts_nothing_and_stays_finite():
    lm, bounded = build("gqa-f32-loop", "bounded", fused=True)
    lengths = np.asarray([900, 700, 5, 9], np.int32)
    toks, pools, sums = run_block(lm, bounded, lengths, np.zeros((B,), bool), np.zeros((B,), bool))
    assert sums.tolist() == [0, 0] and (toks == 0).all()
    assert all(np.isfinite(p).all() for p in pools.values())


@pytest.mark.parametrize("seq,page,want", [
    (4096, 16, (512, 8)), (1024, 16, (128, 8)), (512, 16, (128, 4)), (128, 16, (128, 1)),
    (64, 8, (64, 1)), (4096, 0, (512, 8)), (2048, 128, (256, 8)), (768, 16, (128, 6)),
    (1536, 16, (192, 8)), (32768, 16, (4096, 8))])
def test_the_chunk_rule(seq, page, want):
    """An eighth of the table, not under 128 tokens, whole pages, a divisor."""
    walk = KVWalk(seq, page, jnp.zeros((2,), jnp.int32))
    assert (walk.chunk, walk.n_chunks) == want
    assert walk.chunk * walk.n_chunks == seq and walk.chunk % (page or 1) == 0
    assert walk.loops == (walk.chunk >= 512)


def test_a_model_called_without_live_counts_every_row():
    """``live=None`` (plain ``generate``, the stand-alone step): too wide at
    worst. The stand-alone step of a state whose longest row is retired reads
    as far as that row."""
    idx = jnp.asarray([10, 3000, 7])
    assert int(KVWalk(4096, 16, idx).turns) == 6
    assert int(KVWalk(4096, 16, idx, jnp.asarray([True, False, True])).turns) == 1
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


def test_dataclass_configs_gain_no_field():
    """No new option: the walk is derived from ``max_seq_len`` and
    ``page_size`` alone."""
    names = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert not {n for n in names if "walk" in n or "chunk_tokens" in n or "extent" in n}
    assert not hasattr(llama, "_WALK_FORM")
