"""DeepSeek-V3.2 (``models/deepseek_v32.py``) against its plain reference, tiny
widths, float32, logits and not tokens.

The lightning indexer's 64 heads of 128 become 3 of 16 and ``index_topk`` 2048
becomes 6 (and 100 under a table of 512 slots), BELOW every context here, so
every layer of every row scores, chooses and reads the chosen; 256 sigmoid
experts in 8 groups become 16 in 4 of which 2 are kept by the sum of their two
best ``score + bias``, top-8 becomes top-4, and a "chip" holds 4 of the 16.
Weights are seeded random; the norm scales, the index key's LayerNorm bias and
the router's selection bias are shaken away from their neutral values so that
a term left out shows.

The tolerance: float32 against float32 under ``highest`` precision, 2e-5 of
the reference's largest logit (the right mathematics reads about 1e-6). ONE
token chosen differently moves a layer's output by a sixth of a softmax's
mass, thousands of times the tolerance: logits inside it say the chosen SETS
are the reference's, and ``test_the_choice_is_the_references`` holds the
choice alone to it, ties included.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import deepseek_v32 as reference
from neuronx_distributed_tpu.inference import ServeEngine
from neuronx_distributed_tpu.inference.partition import leaf_partition_spec
from neuronx_distributed_tpu.models import deepseek_v2, deepseek_v32
from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Attention, DeepseekV2Config
from neuronx_distributed_tpu.models.deepseek_v32 import (
    DeepseekV32Attention,
    DeepseekV32Config,
    DeepseekV32ForCausalLM,
    choose_topk,
    index_scores,
)
from neuronx_distributed_tpu.models.llama import INDEX_LEAF, KVLayerView
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.moe.routing import RouterTopK
from tests import tiny
from tests.tiny import IDS, STEPS, at_cached, cached_logits, distance, world

TOL = 2e-5
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0}
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3, num_heads=4,
            num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, first_k_dense=1, moe_intermediate_size=32,
            n_shared_experts=1, router_experts=16, num_experts=4, experts_held_first=4,
            n_group=4, topk_group=2, top_k=4, index_topk=6, index_n_heads=3, index_head_dim=16,
            index_block_q=8, rope_scaling=YARN, max_seq_len=64, dtype=jnp.float32,
            param_dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
            moe_mode="all_experts")
SIZES = {"rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": YARN,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "index_topk": 6,
         "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
         "norm_topk_prob": True, "experts_held_first": 4, "router_experts": 16}
# a table of 512 slots is four chunks of 128: the one-token step reads by
# prefixes and a prompt's blocks reach different ones; top-100 of up to 300
LONG = dict(max_seq_len=512, index_topk=100, index_block_q=64)
LONG_IDS = np.random.RandomState(3).randint(1, 512, (3, 300)).astype(np.int32)
LONG_LENS = np.asarray([290, 140, 205])


full_forward = functools.partial(tiny.full_forward, DeepseekV32ForCausalLM)
serving_lm = functools.partial(tiny.serving_lm, DeepseekV32ForCausalLM,
                               cfg=DeepseekV32Config(**TINY))


def shake(name, a):
    if "e_score_correction_bias" in name or name.endswith("['index_k_norm']['bias']"):
        return a + 0.1 * tiny.noise(name, a)
    return tiny.shake_norms(name, a)


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(DeepseekV32ForCausalLM, DeepseekV32Config(**TINY), IDS, shake)


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))


def test_preset_is_the_published_configuration():
    cfg = deepseek_v32.deepseek_v32()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (61, 7168, 128, 129280)
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (2048, 64, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.n_group, cfg.topk_group, cfg.first_k_dense) == \
        (256, 8, 8, 4, 3)
    assert (cfg.scoring_func, cfg.group_score, cfg.router_selection_bias, cfg.norm_topk_prob) == \
        ("sigmoid", "top2_sum", True, True)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2, rel=1e-6)
    pool = dataclasses.replace(cfg, page_size=16, page_pool_pages=10).kv_leaf_shapes(8)
    assert {n: s for n, (s, _) in pool.items()} == {
        "cached_key": (10, 16, 1, 576), INDEX_LEAF: (10, 16, 1, 128)}


# --------------------------------------------------------------- the forward

@pytest.mark.parametrize("held", ["share", "all"])
def test_full_forward_equals_the_reference(params, want, held):
    world()
    if held == "share":
        assert distance(full_forward(DeepseekV32Config(**TINY), params), want) <= TOL
        return
    cfg = DeepseekV32Config(**dict(TINY, num_experts=16, experts_held_first=0))
    uncut = tiny.make_params(DeepseekV32ForCausalLM, cfg, IDS, shake)
    assert distance(full_forward(cfg, uncut), reference.forward(
        uncut, jnp.asarray(IDS), dict(SIZES, experts_held_first=0))) <= TOL


def _lowest(scores, visible, k):
    return choose_topk(-scores, visible, k)


def _no_relu(q, w, keys):
    dots = jnp.einsum("rjd,td->rjt", q, keys)
    return jnp.einsum("rjt,rj->rt", dots, w)


WRONG = {
    "no_selection": dict(index_topk=None),
    "another_k": dict(index_topk=9),
    "no_group_limit": dict(topk_group=4),
    "no_route_scale": dict(routed_scaling_factor=1.0),
    "not_renormalised": dict(norm_topk_prob=False),
    "no_selection_bias": dict(router_selection_bias=False),
    "softmax_scores": dict(scoring_func="softmax"),
    "no_shared_expert": dict(n_shared_experts=0),
    "plain_rope": dict(rope_scaling=None),
    "another_chips_experts": dict(experts_held_first=8),
    "the_lowest_chosen": ("choose_topk", _lowest),
    "no_relu": ("index_scores", _no_relu),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_wrong_mathematics_fails(params, want, wrong, monkeypatch):
    """Dense attention, another k, the lowest scores chosen, no relu, no group
    limit, no route scale, weights not renormalised, the bias left out of the
    choice, softmax scores, no shared expert, plain rope, another chip's
    experts: each moves the logits far past the tolerance."""
    world()
    fault, tree = WRONG[wrong], params
    if isinstance(fault, tuple):
        monkeypatch.setattr(deepseek_v32, *fault)
        fault = {}
    if "n_shared_experts" in fault:
        block = dict(params["model"]["layers"]["block"])
        block.pop("shared_expert")
        tree = {**params, "model": {**params["model"], "layers": {"block": block}}}
    assert distance(full_forward(DeepseekV32Config(**dict(TINY, **fault)), tree), want) > 10 * TOL


def test_the_bias_is_for_the_choice_alone(params, want, monkeypatch):
    """A reference that WEIGHS by ``score + bias`` is another model."""
    real = reference.route

    def as_weight(z, router, bias, top_k, n_group, topk_group, renormalise, scale):
        chosen = real(z, router, bias, top_k, n_group, topk_group, False, 1.0) > 0
        w = (jax.nn.sigmoid(z @ jnp.asarray(router, jnp.float32)) + bias) * chosen
        return w / jnp.sum(w, axis=-1, keepdims=True) * scale

    monkeypatch.setattr(reference, "route", as_weight)
    low = np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))
    assert distance(low, want) > 10 * TOL


def test_a_lower_precision_fails(params, want):
    rounded = jax.tree.map(lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32), params)
    low = np.asarray(reference.forward(rounded, jnp.asarray(IDS), SIZES))
    assert distance(low, want) > 10 * TOL


# ------------------------------------------------------------------ the choice

@pytest.mark.parametrize("case", ["random", "ties", "fewer_than_k"])
def test_the_choice_is_the_references(case):
    """``choose_topk`` (a threshold and a count of the ties) against the
    reference's stable sort, row by row of a causal triangle: equal sets, a tie
    at the k-th place to the lower slots, no slot past a row's reach."""
    rng = np.random.RandomState(4)
    s, k = 40, 6
    scores = rng.normal(size=(s, s)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores)               # a handful of distinct values
        scores[-1] = 0.0
        scores[-1, [3, 7, 20]] = 5.0            # three above, the rest tie for three places
    if case == "fewer_than_k":
        s, k = 40, 64
    want = np.asarray(reference.chosen_mask(jnp.asarray(scores), k))
    visible = np.tril(np.ones((s, s), bool))
    got = np.asarray(choose_topk(jnp.asarray(scores), jnp.asarray(visible), k))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(np.arange(s) + 1, k)).all() and not (got & ~visible).any()
    if case == "ties":
        assert np.flatnonzero(got[-1]).tolist() == [0, 1, 2, 3, 7, 20]      # the lower ones


def test_the_index_scores_are_the_references():
    rng = np.random.RandomState(5)
    q, k, w = (rng.normal(size=s).astype(np.float32) for s in ((12, 3, 16), (12, 16), (12, 3)))
    want = np.asarray(reference.index_scores(jnp.asarray(q), jnp.asarray(k), jnp.asarray(w)))
    np.testing.assert_allclose(index_scores(jnp.asarray(q), jnp.asarray(w), jnp.asarray(k)),
                               want, rtol=1e-5, atol=1e-5)
    own = np.asarray(index_scores(jnp.asarray(q), jnp.asarray(w),
                                  jnp.broadcast_to(jnp.asarray(k), (12, 12, 16))))
    np.testing.assert_allclose(own, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- the serving path

@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_insert_and_decode_through_both_leaves_equal_the_reference(params, want, cache):
    """Prefill (the blocked masked form past ``index_topk``), then every decoded
    position in the absorbed form under the chosen's mask, against the
    reference's full forward."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params, cache)
    assert distance(cached_logits(lm), at_cached(want)) <= TOL


@pytest.mark.parametrize("heads", ["all_at_once", "in_groups_of_two"])
def test_a_long_table_is_read_by_prefixes_and_chosen_from(params, heads, monkeypatch):
    """512 slots, four chunks: prompts of 140-290 tokens under top-100, their
    blocks of 64 queries reaching different prefixes (the first keeps the
    kernel's result), then steps that read two and three chunks; the prompt's
    attention over all four heads at once, and a group of two at a time."""
    world()
    if heads == "in_groups_of_two":
        monkeypatch.setattr(deepseek_v32, "SCORES_BYTES", 4 * 2 * 64 * 512)
    cfg = DeepseekV32Config(**dict(TINY, **LONG))
    sizes = dict(SIZES, index_topk=LONG["index_topk"])
    steps = 4
    want = np.asarray(reference.forward(params, jnp.asarray(LONG_IDS), sizes))
    with jax.default_matmul_precision("highest"):
        lm = serving_lm(params, cfg=cfg, buckets=(512,)).compile()
    assert distance(cached_logits(lm, LONG_IDS, LONG_LENS, steps),
                    at_cached(want, LONG_LENS, steps)) <= TOL


def _one_layer(cls, cfg, x, steps=1):
    """``x`` (b, s, h) through ONE attention over a slab it owns: all but the
    last ``steps`` tokens at once, then one at a time; (outputs, leaves)."""
    cfg = dataclasses.replace(cfg, decode=True, num_layers=1)
    attn = cls(cfg)
    empty = {name: jnp.zeros((1, *shape), dtype)
             for name, (shape, dtype) in cfg.kv_leaf_shapes(x.shape[0]).items()}

    def run(variables, x, leaves):
        view = KVLayerView(jnp.int32(0), leaves)
        out, mut = attn.apply(variables, x, None, kv=view, mutable=["cache"])
        return out, mut["cache"], view.leaves

    init = attn.init(jax.random.key(0), x, None, kv=KVLayerView(jnp.int32(0), empty))
    weights = {"params": meta.unbox(init["params"])}
    s = x.shape[1]
    with jax.default_matmul_precision("highest"):
        out, cache, leaves = run(weights, x[:, : s - steps], empty)
        outs = [out]
        for t in range(s - steps, s):
            out, cache, leaves = run({**weights, "cache": cache}, x[:, t: t + 1], leaves)
            outs.append(out)
    return jnp.concatenate(outs, axis=1), leaves, weights


def test_the_prompts_form_equals_the_absorbed_form():
    """Sixteen tokens at once (the flash head and the masked blocks) against
    eight at once and eight one at a time (the absorbed step under the mask):
    the same outputs, and both leaves left the same."""
    cfg = DeepseekV32Config(**dict(TINY, index_topk=5))
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.hidden_size), jnp.float32)
    whole, left_whole, _ = _one_layer(DeepseekV32Attention, cfg, x, steps=0)
    stepped, left_steps, _ = _one_layer(DeepseekV32Attention, cfg, x, steps=8)
    np.testing.assert_allclose(stepped, whole, rtol=0, atol=3e-6)
    for name in ("cached_key", INDEX_LEAF):
        np.testing.assert_allclose(left_steps[name], left_whole[name], rtol=0, atol=1e-6)
    assert float(jnp.abs(left_whole[INDEX_LEAF][0, :, :16]).min()) > 0      # written, every slot


def test_a_choice_of_everything_is_deepseek_v2_bit_for_bit(monkeypatch):
    """``index_topk`` at or over the context: the module gives, on the same
    five MLA matrices, what ``DeepseekV2Attention`` gives, bit for bit, in the
    prompt's form over the slab (the branch a continuing row takes, held to
    here on a fresh prompt) and in the one-token step's; a fresh prompt's own
    branch adds up its 12 slots where this module adds the slab's 64, all but
    12 of them masked, and agrees to the order of a sum; and the module still
    writes its keys."""
    cfg = DeepseekV32Config(**dict(TINY, index_topk=64))
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.hidden_size), jnp.float32)
    got, leaves, weights = _one_layer(DeepseekV32Attention, cfg, x, steps=4)
    v2 = DeepseekV2Config(**{k: v for k, v in TINY.items() if not k.startswith("index_")})
    attn = DeepseekV2Attention(dataclasses.replace(v2, decode=True, num_layers=1))
    mla = {k: v for k, v in weights["params"].items() if not k.startswith("index_")}

    def v2s():
        empty = {"cached_key": jnp.zeros((1, 2, 64, 1, v2.latent_dim), jnp.float32)}
        view = KVLayerView(jnp.int32(0), empty)
        with jax.default_matmul_precision("highest"):
            out, mut = attn.apply({"params": mla}, x[:, :12], None, kv=view, mutable=["cache"])
            outs = [out]
            for t in range(12, 16):
                out, mut = attn.apply({"params": mla, "cache": mut["cache"]}, x[:, t: t + 1],
                                      None, kv=view, mutable=["cache"])
                outs.append(out)
        return jnp.concatenate(outs, axis=1), view.leaves["cached_key"]

    fresh, left = v2s()
    np.testing.assert_allclose(np.asarray(got[:, :12]), np.asarray(fresh[:, :12]),
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(got[:, 12:]), np.asarray(fresh[:, 12:]))
    np.testing.assert_array_equal(np.asarray(leaves["cached_key"]), np.asarray(left))
    prompt_rows = deepseek_v2._prompt_rows
    monkeypatch.setattr(deepseek_v2, "_prompt_rows", lambda cls, cfg, continues, *rest: prompt_rows(
        cls, cfg, jnp.bool_(True), *rest))
    over_the_slab, left = v2s()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(over_the_slab))
    np.testing.assert_array_equal(np.asarray(leaves["cached_key"]), np.asarray(left))
    assert float(jnp.abs(leaves[INDEX_LEAF][0, :, :16]).min()) > 0


def test_without_an_indexer_the_module_is_deepseek_v2s():
    """``index_topk=None``: no index parameters, no second leaf, and the
    lowered attention is ``DeepseekV2Attention``'s text (Rule 5's seam)."""
    cfg = DeepseekV32Config(**dict(TINY, index_topk=None))
    v2 = DeepseekV2Config(**{k: v for k, v in TINY.items() if not k.startswith("index_")})
    assert list(cfg.kv_leaf_shapes(2)) == ["cached_key"] and not cfg.prompt_live
    x = jnp.zeros((2, 8, 64), jnp.float32)
    texts = []
    for cls, c in ((DeepseekV32Attention, cfg), (DeepseekV2Attention, v2)):
        c = dataclasses.replace(c, decode=True, num_layers=1)
        attn = cls(c)
        empty = {"cached_key": jnp.zeros((1, 2, 64, 1, c.latent_dim), jnp.float32)}
        init = jax.eval_shape(lambda: attn.init(
            jax.random.key(0), x, None, kv=KVLayerView(jnp.int32(0), empty)))
        assert not [k for k in init["params"] if k.startswith("index_")]
        for tokens in (8, 1):
            def run(variables, x, leaves):
                view = KVLayerView(jnp.int32(0), leaves)
                return attn.apply(variables, x, None, kv=view, mutable=["cache"]), view.leaves
            texts.append(jax.jit(run).lower(meta.unbox(init), x[:, :tokens], empty).as_text())
    assert texts[0] == texts[2] and texts[1] == texts[3]


def test_both_leaves_are_declared_counted_and_replicated(params):
    world()
    from jax.sharding import PartitionSpec

    lm = serving_lm(params, cfg=DeepseekV32Config(**dict(TINY, dtype=jnp.bfloat16)))
    leaves = {jax.tree_util.keystr(p): a for p, a in
              jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]}
    pools = {p: a.shape for p, a in leaves.items() if "cached" in p}
    pages = lm.config.page_pool_pages
    assert pools == {"['model']['cached_key']": (3, pages, 8, 1, 40),
                     f"['model']['{INDEX_LEAF}']": (3, pages, 8, 1, 16)}
    assert lm.kv_cache_bytes()["kv_bytes"] / (3 * pages * 8) == 2 * (40 + 16)
    assert lm.kv_page_bytes() == lm.kv_page_bytes_host() == 3 * 8 * 2 * (40 + 16)
    assert leaf_partition_spec(f"['model']['{INDEX_LEAF}']", (5, 520, 16, 1, 128), 4) == \
        PartitionSpec()
    assert lm.walk_sum_names == ("dsa_tokens_visible", "dsa_tokens_selected",
                                 "dsa_latent_slots_read") and lm.walk_sums == 6


REFUSED = {
    "int8_pages": (lambda p: serving_lm(p, page_dtype="int8"), "latent"),
    "a_key_narrower_than_its_rotary": (
        lambda p: DeepseekV32Config(**dict(TINY, index_head_dim=4)), INDEX_LEAF),
    "no_chosen_token": (lambda p: DeepseekV32Config(**dict(TINY, index_topk=0)), "index_topk"),
    "a_bias_with_groups_scored_by_their_max": (
        lambda p: RouterTopK(16, n_group=4, topk_group=2, selection_bias=True).init(
            jax.random.key(0), jnp.zeros((2, 8))), "top2_sum"),
    "an_unknown_group_score": (
        lambda p: RouterTopK(16, n_group=4, topk_group=2, group_score="mean").init(
            jax.random.key(0), jnp.zeros((2, 8))), "group_score"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_cannot_be_served_is_refused_by_name(params, what):
    world()
    build, names = REFUSED[what]
    with pytest.raises(ValueError, match=names):
        build(params)


def test_serve_engine_shares_both_leaves_of_a_prefix_and_counts_the_choice(params):
    """Five requests, greedy, two sharing a 16-token prefix with an earlier
    one: each gets the tokens ``generate`` gives it alone (an insert over a
    shared prefix, index keys included, equals a fresh insert), page IO reads
    both leaves, and the counters say what was visible, chosen and read."""
    world()
    rng = np.random.RandomState(5)
    shared = rng.randint(1, 512, (16,)).astype(np.int32)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32) for n in (9, 20, 13)]
    prompts += [np.concatenate([shared, rng.randint(1, 512, (n,)).astype(np.int32)])
                for n in (5, 9)]
    with jax.default_matmul_precision("highest"):
        alone = tiny.compiled_lm(serving_lm, params, "slab")   # generate() is the slab path's
        solo = [alone.generate(p[None], STEPS + 1).tokens[0] for p in prompts]
        lm = tiny.compiled_lm(serving_lm, params)
        engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0))
        ids = [engine.submit(p, max_new_tokens=STEPS + 1, arrival_block=0) for p in prompts[:4]]
        while engine.step_block():
            pass
        ids.append(engine.submit(prompts[4], max_new_tokens=STEPS + 1, arrival_block=engine.blocks))
        while engine.step_block():
            pass
    assert not engine.rejected
    done = {c.request_id: np.asarray(c.tokens) for c in engine.completed}
    for rid, alone in zip(ids, solo):
        np.testing.assert_array_equal(done[rid], alone)
    assert engine.session.paged.stats["prefix_hits"] > 0
    page = engine._read_page_bytes(1)
    assert sorted(k[k.rindex("['"):] for k in page) == ["['cached_index_key']", "['cached_key']"]
    stats = engine.stats
    layers, topk = 3, 6
    visible, chosen, read = (stats[k] for k in ("dsa_tokens_visible", "dsa_tokens_selected",
                                                "dsa_latent_slots_read"))
    # every live row past the sixth token: six chosen a layer-step
    assert 0 < chosen < visible < read and chosen % (layers * topk) == 0
    assert read == layers * stats["kv_walk_row_slots"]


# ------------------------------------------------------------------ the router

def loop_router(scores, bias, n_group, topk_group, top_k):
    """``noaux_tc`` one token at a time: (tokens, experts) 0/1."""
    T, E = scores.shape
    size = E // n_group
    chosen = np.zeros((T, E), bool)
    for t in range(T):
        c = scores[t] + bias
        group = [sum(sorted(c[g * size: (g + 1) * size])[-2:]) for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: (-group[g], g))[:topk_group]
        eligible = [e for e in range(E) if e // size in groups]
        for e in sorted(eligible, key=lambda e: (-c[e], e))[:top_k]:
            chosen[t, e] = True
    return chosen


@pytest.mark.parametrize("case", ["random", "the_bias_flips_a_group", "two_beat_one"])
def test_the_route_equals_a_hand_written_noaux_tc(case):
    """16 experts in 4 groups, 2 kept by the sum of their two best ``score +
    bias``, top-4 of ``score + bias`` inside them, weights the SCORES of the
    chosen over their sum, times 2.5; against a loop, and against the
    reference's route."""
    rng = np.random.RandomState(11)
    logits = rng.normal(size=(32, 16)).astype(np.float32)
    bias = (0.2 * rng.normal(size=(16,))).astype(np.float32)
    if case == "the_bias_flips_a_group":
        logits[:, :] = -2.0
        logits[:, [0, 1]], logits[:, [4, 5]], logits[:, [8, 9]] = 2.0, 1.5, 1.0
        bias[:] = 0.0
        bias[[8, 9]] = 0.5                      # group 2 passes groups 0 and 1 by its bias
    elif case == "two_beat_one":
        logits[:, :] = -3.0
        logits[:, 0] = 6.0                      # one very strong expert: sigmoid ~ 1
        logits[:, [4, 5]], logits[:, [8, 9]] = 0.5, 0.6     # two middling ones each: 1.2+
        bias[:] = 0.0
    router = RouterTopK(16, top_k=4, norm_topk_prob=True, n_group=4, topk_group=2,
                        route_scale=2.5, scoring_func="sigmoid", selection_bias=True,
                        group_score="top2_sum")
    weights = {"params": {"kernel": jnp.eye(16, dtype=jnp.float32),
                          "e_score_correction_bias": jnp.asarray(bias)}}
    combine, _ = router.apply(weights, jnp.asarray(logits))
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    chosen = loop_router(scores, bias, 4, 2, 4)
    np.testing.assert_array_equal(np.asarray(combine) > 0, chosen)
    kept = scores * chosen
    np.testing.assert_allclose(np.asarray(combine), 2.5 * kept / kept.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(
        reference.route(jnp.asarray(logits), np.eye(16, dtype=np.float32), bias, 4, 4, 2, True,
                        2.5), np.asarray(combine), rtol=1e-6)
    if case == "the_bias_flips_a_group":
        assert chosen[0].nonzero()[0].tolist()[-2:] == [8, 9] and not chosen[:, [4, 5]].any()
    if case == "two_beat_one":
        assert not chosen[:, 0].any()           # its group's two best sum to less


def test_the_shares_add_up_to_the_uncut_layer(params):
    """32 "chips" hold one expert each of a layer's 32 (8 groups of 4, 4 kept,
    top-8). The routed parts they compute (weights renormalised over all eight
    chosen BEFORE the absent experts' picks are dropped) plus the shared
    expert, counted once, are what the uncut reference gives for the layer."""
    world()
    rng = np.random.RandomState(2)
    z = rng.normal(size=(2, 10, 64)).astype(np.float32)
    gate, up, down = (rng.normal(size=s).astype(np.float32) * 0.2
                      for s in ((32, 64, 32), (32, 64, 32), (32, 32, 64)))
    router = rng.normal(size=(64, 32)).astype(np.float32)
    bias = (0.1 * rng.normal(size=(32,))).astype(np.float32)
    shared = jax.tree.map(lambda a: a[0], params["model"]["layers"]["block"]["shared_expert"])

    def share(first, held):
        moe = MoE(num_experts=held, hidden_size=64, intermediate_size=32, top_k=8,
                  norm_topk_prob=True, dtype=jnp.float32, inference=True,
                  router_experts=None if held == 32 else 32, experts_held_first=first,
                  n_group=8, topk_group=4, route_scale=2.5, scoring_func="sigmoid",
                  selection_bias=True, group_score="top2_sum")
        tree = {"router": {"kernel": router, "e_score_correction_bias": bias},
                "experts": {k: w[first: first + held] for k, w in
                            (("gate", gate), ("up", up), ("down", down))}}
        with jax.default_matmul_precision("highest"):
            return np.asarray(moe.apply({"params": tree}, jnp.asarray(z)))

    with jax.default_matmul_precision("highest"):
        combine = reference.route(jnp.asarray(z), router, bias, 8, 8, 4, True, 2.5)
        np.testing.assert_allclose(np.asarray(combine).sum(-1), 2.5, rtol=1e-5)
        once = reference._mlp_add(jnp.zeros_like(z), jnp.asarray(z), shared)
        uncut = once
        for e in range(32):
            uncut = reference._expert_add(uncut, jnp.asarray(z), combine[..., e], gate[e], up[e],
                                          down[e])
    parts = [share(first, 1) for first in range(32)]
    assert sum(np.abs(p).max() > 0 for p in parts) > 16         # most chips had work
    assert distance(sum(parts) + np.asarray(once), np.asarray(uncut)) <= TOL
    assert distance(share(0, 32) + np.asarray(once), np.asarray(uncut)) <= TOL
