"""LongCat-Flash (``models/longcat_flash.py``) against its plain reference, tiny
widths, float32, logits and not tokens.

64 heads of latent attention become 4 (latent 32 + 8 rotary, q rank 48, so the
two low-rank scales are sqrt(64 / 48) and sqrt(2)), 512 real + 256 identity
experts become 16 + 8, top-12 becomes top-6 at the published scale 6, two
layers (four attention sub-layers, four dense MLPs, two expert layers), and a
"chip" holds 4 of the 16 real experts. Weights are seeded random; the norm
scales are shaken away from one and the selection bias away from zero, so that
a scale on the wrong axis or a bias that leaks into the weights shows.

The tolerance: float32 against float32 under ``highest`` matmul precision, so
only the order of additions differs (the absorbed decode, the grouped matmul):
logits within 2e-5 of the reference's largest; the right mathematics reads
4e-7 to 6e-7. Each wrong mathematics below names the margin it has to clear.
"""

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark.reference import longcat_flash as reference
from neuronx_distributed_tpu.inference import ServeEngine
from neuronx_distributed_tpu.models.llama import KVLayerView
from neuronx_distributed_tpu.models.longcat_flash import (
    LongcatFlashConfig,
    LongcatFlashForCausalLM,
    LongcatFlashLayer,
    LongcatFlashSubLayer,
    longcat_flash_chat,
)
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.moe.routing import RouterTopK
from tests import tiny
from tests.tiny import IDS, LENS, STEPS, at_cached, cached_logits, distance, world

TOL = 2e-5
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=2, num_heads=4,
            num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32, router_experts=16,
            zero_experts=8, num_experts=4, experts_held_first=4, top_k=6, rope_theta=1e7,
            max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None, moe_mode="all_experts")
SIZES = {"rms_norm_eps": 1e-5, "rope_theta": 1e7, "hidden_size": 64, "q_lora_rank": 48,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "moe_topk": 6,
         "routed_scaling_factor": 6.0, "router_experts": 16, "zero_expert_num": 8,
         "experts_held_first": 4}
full_forward = functools.partial(tiny.full_forward, LongcatFlashForCausalLM)
serving_lm = functools.partial(tiny.serving_lm, LongcatFlashForCausalLM,
                               cfg=LongcatFlashConfig(**TINY))


def shake(name, a):
    if "e_score_correction_bias" in name:   # softmax scores over 24 sit near 0.04
        return 0.05 * jax.random.normal(jax.random.key(7), a.shape)
    return tiny.shake_norms(name, a)


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(LongcatFlashForCausalLM, LongcatFlashConfig(**TINY), IDS, shake)


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))


def test_preset_is_the_published_configuration():
    cfg = longcat_flash_chat()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (28, 6144, 64, 131072)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.latent_dim, cfg.head_dim_) == (512, 1536, 576, 192)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (12288, 2048)
    assert (cfg.num_experts, cfg.zero_experts, cfg.top_k, cfg.routed_scaling_factor) == (512, 256, 12, 6.0)
    assert (cfg.q_lora_scale, cfg.kv_lora_scale) == (2.0, 12 ** 0.5)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5) and cfg.rope_theta == 1e7
    assert cfg.kv_layers == 56 and cfg.rope_scaling is None and cfg.first_k_dense == 0


def test_the_parameter_and_cache_trees_hold_two_sub_layers_a_layer(params):
    """Two attentions, two dense MLPs, four norms and one expert layer a layer;
    ONE latent leaf over 2 x num_layers, a cache_index and a block_table a
    sub-layer, no second pool."""
    world()
    block = params["model"]["layers"]["block"]
    assert sorted(block) == ["moe", "sub_0", "sub_1"]
    assert sorted(block["sub_1"]) == ["attention", "input_norm", "mlp", "post_attn_norm"]
    assert block["moe"]["router"]["kernel"].shape == (2, 64, 24)
    assert block["moe"]["router"]["e_score_correction_bias"].shape == (2, 24)
    assert block["moe"]["experts"]["gate"].shape == (2, 4, 64, 32)
    lm = serving_lm(params)
    cache = {jax.tree_util.keystr(p): a.shape for p, a in
             jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]}
    pages = lm.config.page_pool_pages
    assert cache.pop("['model']['cached_key']") == (4, pages, 8, 1, 40)
    for i in (0, 1):
        at = f"['model']['layers']['block']['sub_{i}']['attention']"
        assert cache.pop(at + "['cache_index']") == (2, 4)
        assert cache.pop(at + "['block_table']") == (2, 4, 8)
    assert not cache
    assert lm.kv_cache_bytes()["kv_bytes"] == 4 * pages * 8 * 40 * 4
    assert (lm.moe_sums, lm.moe_width, lm.moe_share) == (5, 24, True)


# --------------------------------------------------------------- the forward

@pytest.mark.parametrize("held", ["share", "all"])
def test_full_forward_equals_the_reference(params, want, held):
    world()
    if held == "share":
        assert distance(full_forward(LongcatFlashConfig(**TINY), params), want) <= TOL
        return
    cfg = LongcatFlashConfig(**dict(TINY, num_experts=16, experts_held_first=0, router_experts=None))
    uncut = tiny.make_params(LongcatFlashForCausalLM, cfg, IDS, shake)
    sizes = dict(SIZES, experts_held_first=0)
    assert distance(full_forward(cfg, uncut),
                    reference.forward(uncut, jnp.asarray(IDS), sizes)) <= TOL


class _WrongLayer(LongcatFlashLayer):
    """The layer's six lines with one of two mistakes a config cannot make:
    the expert branch taken after sub-block ``branch_after`` instead of 0, or
    sub-layer ``i`` addressing cache leaf ``2 l + leaf[i]``."""

    branch_after: int = 0
    leaf: tuple = (0, 1)

    @nn.compact
    def __call__(self, x, rope, kv=None, live=None, stack=None):
        m = None
        for i in range(2):
            sub = LongcatFlashSubLayer(self.config, name=f"sub_{i}")
            view = None if kv is None else KVLayerView(2 * kv.layer + self.leaf[i], kv.leaves)
            x, u = sub.attend(x, rope, view, live)
            if view is not None:
                kv.leaves = view.leaves
            if i == self.branch_after:
                m = self._experts(u, live, None if stack is None else (kv.layer, stack))
            x = sub.feed(x, u)
        return x + m


def _wrong_model(**fields):
    class Layer(_WrongLayer):
        branch_after: int = fields.get("branch_after", 0)
        leaf: tuple = fields.get("leaf", (0, 1))

    class Model(LongcatFlashForCausalLM):
        layer_cls: Any = Layer

    return Model


# each wrong mathematics, and the least it has to move the logits (relative to
# the reference's largest): read once at these widths (0.50, 0.43, 0.24, 0.41)
# and halved
WRONG = {
    "no_scale_6": (dict(routed_scaling_factor=1.0), {}, 0.25),
    "lora_scales_left_out": (dict(mla_scale_q_lora=False, mla_scale_kv_lora=False), {}, 0.2),
    "bias_left_out_of_the_choice": (dict(router_selection_bias=False), {}, 0.12),
    "branch_fed_sub_block_1": ({}, dict(branch_after=1), 0.2),
}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_wrong_mathematics_fails(params, want, wrong):
    world()
    over, layer, margin = WRONG[wrong]
    cfg = LongcatFlashConfig(**dict(TINY, **over))
    tree = params
    if wrong == "bias_left_out_of_the_choice":
        router = dict(params["model"]["layers"]["block"]["moe"]["router"])
        router.pop("e_score_correction_bias")
        block = {**params["model"]["layers"]["block"],
                 "moe": {**params["model"]["layers"]["block"]["moe"], "router": router}}
        tree = {**params, "model": {**params["model"], "layers": {"block": block}}}
    got = tiny.full_forward(_wrong_model(**layer), cfg, tree)
    assert distance(got, want) > margin > 100 * TOL


def test_the_identity_part_left_out_fails(params, want, monkeypatch):
    """The comparison from the other side: a reference whose identity experts
    add nothing is far from the program."""
    world()
    route = reference.route
    monkeypatch.setattr(reference, "route",
                        lambda *a: route(*a).at[..., SIZES["router_experts"]:].set(0.0))
    without = np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))
    assert distance(full_forward(LongcatFlashConfig(**TINY), params), without) > 0.3 > 100 * TOL
    assert distance(without, want) > 0.3         # reads 0.67


def test_a_lower_precision_fails(params, want, monkeypatch):
    """The control: the reference itself with every weight rounded to bf16
    lands far outside the float32 tolerance."""
    world()
    monkeypatch.setattr(reference, "f32", lambda tree: jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32), tree))
    jax.clear_caches()
    rounded = np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))
    jax.clear_caches()
    assert distance(rounded, want) > 100 * TOL


@pytest.mark.parametrize("refused", [dict(first_k_dense=1), dict(n_shared_experts=2),
                                     dict(n_group=4, topk_group=2),
                                     dict(rope_scaling={"type": "yarn", "factor": 40}),
                                     dict(page_dtype="int8"), dict(experts_held_first=14),
                                     dict(zero_experts=-1)],
                         ids=lambda r: next(iter(r)))
def test_what_the_layer_does_not_hold_is_refused(refused):
    with pytest.raises(ValueError):
        LongcatFlashConfig(**dict(TINY, **refused))


# ------------------------------------------------------------- the serving path

@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_insert_and_decode_through_both_sub_layers_leaves_equal_the_reference(params, want, cache):
    """Prefill in the expanded form, then every decoded position in the
    absorbed form over the cached latent of BOTH sub-layers, against the
    reference's full forward (which never takes the absorbed form)."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params, cache)
    session = lm.start_session()
    assert distance(cached_logits(lm, session=session), at_cached(want)) <= TOL
    if cache == "paged":
        # (touched, assigned, layer calls, every pick, identity picks, the passes' three)
        _, assigned, calls, routed, zero, *_ = np.asarray(session.insert_routing)
        assert calls == 2 and routed == 2 * LENS.sum() * 6
        assert 0 < zero < routed and 0 < assigned <= routed - zero


def test_sub_layer_1_reading_sub_layer_0s_leaf_fails(params, want):
    """Both sub-layers on leaf 2 l: the prompt (which attends over itself
    through the cache it has just written) and every step after it go wrong
    (0.94 of the reference's largest logit where the right leaves read 6e-7)."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.serving_lm(_wrong_model(leaf=(0, 0)), params, LongcatFlashConfig(**TINY)).compile()
    assert distance(cached_logits(lm), at_cached(want)) > 0.45 > 100 * TOL


def test_serve_engine_counts_the_picks_that_cost_nothing_and_hits_a_latent_prefix(params):
    """Through ``ServeEngine``: greedy tokens equal ``generate``'s alone, a
    second request with the first's prompt as its prefix re-uses its latent
    pages in both sub-layers' leaves, and the counters tell an identity pick
    from one that costs a product."""
    world()
    prompt = IDS[0, :20]
    longer = np.concatenate([prompt[:16], IDS[1, :6]])
    with jax.default_matmul_precision("highest"):
        alone = tiny.compiled_lm(serving_lm, params, "slab")   # generate() is the slab path's
        solo = [alone.generate(p[None], STEPS + 1).tokens[0] for p in (prompt, longer)]
        lm = tiny.compiled_lm(serving_lm, params)
        engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0))
        ids = []
        for p in (prompt, longer):      # one row live at a time
            ids.append(engine.submit(p, max_new_tokens=STEPS + 1, arrival_block=engine.blocks))
            while engine.step_block():
                pass
    assert not engine.rejected
    done = {c.request_id: np.asarray(c.tokens) for c in engine.completed}
    for rid, tokens in zip(ids, solo):
        np.testing.assert_array_equal(done[rid], tokens)
    assert engine.session.paged.stats["prefix_hits"] >= 1
    stats = engine.stats
    # the ONE live row of a step picks top_k of 24 in each of the 2 expert layers
    assert stats["moe_assignments_routed"] == stats["moe_layer_steps"] * 6 > 0
    assert 0 < stats["moe_zero_picks"] < stats["moe_assignments_routed"]
    assert stats["moe_assignments"] <= stats["moe_assignments_routed"] - stats["moe_zero_picks"]
    assert stats["moe_insert_assignments_routed"] == 2 * 6 * (20 + 22 - 16)
    assert 0 < stats["moe_insert_zero_picks"] < stats["moe_insert_assignments_routed"]
    assert (stats["moe_insert_assignments"]
            <= stats["moe_insert_assignments_routed"] - stats["moe_insert_zero_picks"])


# ------------------------------------------------------------ the expert layer

RNG = np.random.RandomState(2)
Z = RNG.normal(size=(2, 10, 64)).astype(np.float32)
GATE, UP, DOWN = (RNG.normal(size=s).astype(np.float32) * 0.2
                  for s in ((16, 64, 32), (16, 64, 32), (16, 32, 64)))
ROUTER = RNG.normal(size=(64, 24)).astype(np.float32)
BIAS = (0.05 * RNG.normal(size=(24,))).astype(np.float32)


def expert_layer(first, held, router=ROUTER, bias=BIAS, z=Z, weights=(GATE, UP, DOWN), live=None,
                 **kw):
    """``(output, what it sowed)`` of serving's grouped path on a share told
    what it holds."""
    moe = MoE(num_experts=held, hidden_size=64, intermediate_size=32, top_k=6,
              norm_topk_prob=False, dtype=jnp.float32, inference=True,
              router_experts=None if held == 16 else 16, experts_held_first=first,
              route_scale=6.0, zero_experts=8, selection_bias=True, **kw)
    tree = {"router": {"kernel": router, "e_score_correction_bias": bias},
            "experts": {k: w[first: first + held] for k, w in zip(("gate", "up", "down"), weights)}}
    with jax.default_matmul_precision("highest"):
        out, sown = moe.apply({"params": tree}, jnp.asarray(z),
                              None if live is None else jnp.asarray(live),
                              mutable=["moe_stats", "losses"])
    return np.asarray(out), jax.tree.map(lambda a: np.asarray(a), sown["moe_stats"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Four "chips" hold real experts 0-3, 4-7, 8-11 and 12-15 of one layer's
    16 beside its 8 identity experts. Every chip adds the identity part for
    its own rows in full, so a share's ROUTED part is its output less that;
    the four routed parts plus the identity part, counted once, are what the
    uncut reference gives for the whole layer."""
    world()
    z = jnp.asarray(Z)
    with jax.default_matmul_precision("highest"):
        combine = reference.route(z, ROUTER, BIAS, 6, 6.0)
        identity = np.asarray(jnp.sum(combine[..., 16:], axis=-1, keepdims=True) * z)
        uncut = jnp.asarray(identity)
        for e in range(16):
            uncut = reference.expert_add(uncut, z, combine[..., e], GATE[e], UP[e], DOWN[e])
    shares = [expert_layer(first, 4)[0] for first in (0, 4, 8, 12)]
    routed = [s - identity for s in shares]
    assert all(np.abs(r).max() > 1e-2 for r in routed)          # every chip had work
    assert np.abs(identity).max() > 1e-2
    assert distance(sum(routed) + identity, np.asarray(uncut)) <= TOL
    assert distance(sum(shares), np.asarray(uncut)) > 0.05      # the identity part four times
    assert distance(expert_layer(0, 16)[0], np.asarray(uncut)) <= TOL   # every real expert held


def test_the_bias_moves_the_choice_and_not_the_weights():
    world()
    flat = jnp.asarray(Z.reshape(-1, 64))
    router = RouterTopK(24, top_k=6, norm_topk_prob=False, route_scale=6.0, selection_bias=True)
    probs = np.asarray(jax.nn.softmax(flat @ ROUTER, axis=-1))

    def gates(bias):
        tree = {"kernel": ROUTER, "e_score_correction_bias": jnp.asarray(bias)}
        return np.asarray(router.apply({"params": tree}, flat)[0])

    plain, pushed = gates(np.zeros(24, np.float32)), gates(np.eye(24, dtype=np.float32)[5] * 10)
    assert not (plain[:, 5] > 0).all() and (pushed[:, 5] > 0).all()      # the choice moved
    assert ((pushed > 0).sum(-1) == 6).all()
    np.testing.assert_allclose(pushed, 6.0 * probs * (pushed > 0), rtol=1e-6)   # the weights did not
    np.testing.assert_allclose(plain, 6.0 * probs * (plain > 0), rtol=1e-6)
    np.testing.assert_array_equal(gates(BIAS) > 0, np.asarray(
        reference.route(flat, ROUTER, BIAS, 6, 6.0)) > 0)
    with pytest.raises(ValueError, match="n_group"):
        RouterTopK(24, top_k=6, n_group=4, topk_group=2, selection_bias=True).init(
            jax.random.key(0), flat)


def test_a_token_whose_picks_are_all_identity_gets_its_input_back_and_no_product():
    """Six identity experts biased far ahead: every pick of every token is an
    identity one. The layer returns ``6 (sum of the six scores) u``, sows no
    chosen expert, and never reads the experts' weights (they are NaN)."""
    world()
    bias = np.zeros(24, np.float32)
    bias[16:22] = 10.0
    nan = tuple(np.full_like(w, np.nan) for w in (GATE, UP, DOWN))
    out, sown = expert_layer(4, 4, bias=bias, weights=nan)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(Z) @ ROUTER, axis=-1))
    np.testing.assert_allclose(out, 6.0 * probs[..., 16:22].sum(-1, keepdims=True) * Z, rtol=2e-6)
    assert not np.asarray(sown["chosen"][0]).any()
    assert (np.asarray(sown["zero"][0]) == 6).all() and (np.asarray(sown["routed"][0]) == 6).all()
    # a token that is not live comes out zero and is counted by whoever reads `live`
    live = np.ones((2, 10), bool)
    live[1, 3:] = False
    masked, _ = expert_layer(4, 4, bias=bias, live=live)
    assert not masked[1, 3:].any() and masked[1, :3].any()


def test_zero_experts_and_a_bias_need_the_top_k_router():
    with pytest.raises(ValueError, match="top_k"):
        MoE(num_experts=4, hidden_size=64, intermediate_size=32, router="sinkhorn",
            zero_experts=8).init(jax.random.key(0), jnp.asarray(Z))
