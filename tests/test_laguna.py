"""Laguna through the serving path against its plain reference.

Tiny widths, float32, seeded weights, on the CPU: five layers ``f s s s f``
(layer 0 dense), 4 / 6 query heads over 2 KV heads of 8, a window of 8 in a
ring of 24 (8 + a page of 16), 8 of 16 experts held, top-3 by sigmoid, a
shared expert; the full layers rotate half a head by YaRN whose original
context is 16. ``benchmark/reference/laguna.py`` is a dense (T, T) mask a
layer and no cache; the program inserts through pages and rings and decodes
one token a step. Logits, relative to the reference's largest.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.sharding import PartitionSpec

from benchmark.reference import laguna as reference
from neuronx_distributed_tpu.inference import CausalLM, ServeEngine, causal_lm
from neuronx_distributed_tpu.inference.partition import leaf_partition_spec
from neuronx_distributed_tpu.models.laguna import (
    FULL,
    SLIDING,
    WINDOW_KEY,
    LagunaConfig,
    LagunaForCausalLM,
    laguna_s_2_1,
)
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.moe.routing import RouterTopK, group_limit
from tests import tiny
from tests.tiny import distance, padded, world

TOL = 2e-5
WINDOW, RING, ORIGINAL = 8, 24, 16
ROPE = {FULL: dict(rope_type="yarn", rope_theta=500000.0, factor=8.0,
                   original_max_position_embeddings=ORIGINAL, beta_fast=32.0, beta_slow=1.0,
                   attention_factor=1.2, partial_rotary_factor=0.5),
        SLIDING: dict(rope_type="default", rope_theta=10000.0, partial_rotary_factor=1.0)}
TYPES = [FULL, SLIDING, SLIDING, SLIDING, FULL]
TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=5, num_heads=4,
            num_kv_heads=2, head_dim=8, max_seq_len=64, layer_types=TYPES * 2,
            num_heads_per_layer=[4, 6, 6, 6, 4] * 2, sliding_window=WINDOW,
            moe_intermediate_size=16, shared_expert_intermediate_size=16, num_experts=8,
            router_experts=16, top_k=3, rope_parameters=ROPE, dtype=jnp.float32,
            param_dtype=jnp.float32, use_flash_attention=False)
# the same sizes under the configuration file's key names, for the reference
SIZES = dict(num_hidden_layers=5, layer_types=TYPES * 2, head_dim=8, num_key_value_heads=2,
             rms_norm_eps=1e-6, sliding_window=WINDOW, rope_parameters=ROPE, gating="per-head",
             num_experts_per_tok=3, norm_topk_prob=True, moe_routed_scaling_factor=2.5,
             scoring_func="sigmoid", experts_held_first=0, mlp_only_layers=[0])
IDS = np.random.RandomState(0).randint(1, 128, (3, 60)).astype(np.int32)
LENS = np.asarray([24, 11])            # both longer than the window
STEPS = 30                             # row 0 reaches 54: past the ring's 24 twice


serving_lm = functools.partial(tiny.serving_lm, LagunaForCausalLM, cfg=LagunaConfig(**TINY),
                               buckets=(16, 32), page_size=16, prefix_cache=False)


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(LagunaForCausalLM, LagunaConfig(**TINY), seed=0)


@pytest.fixture(scope="module")
def lm(params):
    world()
    return serving_lm(params).compile()


@pytest.fixture(scope="module")
def want(params):
    """The reference's logits over rows 0 and 1 of IDS, every position."""
    return np.asarray(reference.forward(params, jnp.asarray(IDS[:2]), SIZES))


@pytest.fixture(scope="module")
def served(lm):
    """Rows 0 and 1 inserted at LENS into slots 0 and 1, then STEPS
    teacher-forced steps: ``(session, insert logits (2, V), step logits
    (STEPS, 2, V))``."""
    world()
    session = lm.start_session()
    first = np.asarray(lm.insert(session, np.arange(2), padded(IDS, [0, 1], LENS), lengths=LENS,
                                 reserve_tokens=STEPS + 2))
    steps = []
    for t in range(STEPS):
        tok = np.zeros((4,), np.int32)
        tok[:2] = [IDS[r, LENS[r] + t] for r in (0, 1)]
        steps.append(np.asarray(lm.step(session, tok))[:2])
    return session, first, np.stack(steps)


def reference_steps(want, lo, hi):
    """The reference's logits at the positions steps ``lo .. hi - 1`` answer."""
    return np.stack([[want[r, LENS[r] + t] for r in (0, 1)] for t in range(lo, hi)])


# ------------------------------------------------ the path against the reference

PHASES = {
    # steps [lo, hi): row 0 stands at 24 + t, row 1 at 11 + t
    "before_the_ring_wraps": (0, RING - 11),           # row 1 below 24, row 0 in its 2nd lap
    "past_the_wrap": (RING - 11, RING),                 # row 1 wraps, row 0 nears 48
    "two_laps_and_past_the_original_context": (RING, STEPS),
}


def test_a_prompts_last_logits_agree(served, want):
    """Rows of different lengths, both longer than the window, one padded
    to its bucket: the ring takes the last real tokens, not the padding."""
    _, first, _ = served
    scale = np.abs(want).max()
    for r in (0, 1):
        assert distance(first[r], want[r, LENS[r] - 1], scale) <= TOL


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_decode_through_pages_and_rings_agrees(served, want, phase):
    assert ORIGINAL < LENS[0] and LENS[1] + STEPS > RING and LENS[0] + STEPS > 2 * RING
    lo, hi = PHASES[phase]
    _, _, steps = served
    assert distance(steps[lo:hi], reference_steps(want, lo, hi), np.abs(want).max()) <= TOL


def test_a_retired_slot_reused_by_a_shorter_prompt_does_not_see_its_last_tenant(lm, served, params):
    """Slot 0 held 54 tokens; its next tenant brings 9, fewer than the ring, so
    15 ring slots still hold the old tenant's keys: the mask goes by the
    position a slot holds, and they hold none yet."""
    world()
    session, _, _ = served
    lm.retire(session, [0])
    got = [np.asarray(lm.insert(session, np.asarray([0]), IDS[2:3, :9], lengths=np.asarray([9]),
                                reserve_tokens=8))[0]]
    for t in range(4):
        tok = np.zeros((4,), np.int32)
        tok[0] = IDS[2, 9 + t]
        got.append(np.asarray(lm.step(session, tok))[0])
    ref = np.asarray(reference.forward(params, jnp.asarray(IDS[2:3, :13]), SIZES))[0]
    assert distance(np.stack(got), ref[8:13], np.abs(ref).max()) <= TOL


def test_a_dead_rows_ring_is_left_alone(lm, params):
    """A step writes the rings of its live rows alone: a slot that is not
    active keeps every byte (its ring is its next tenant's or nobody's)."""
    world()
    session = lm.start_session()
    lm.insert(session, np.arange(2), padded(IDS, [0, 1], LENS), lengths=LENS, reserve_tokens=8)

    def ring(slot):
        return np.asarray(next(leaf for path, leaf in
                               jax.tree_util.tree_flatten_with_path(session.cache)[0]
                               if jax.tree_util.keystr(path).endswith(f"['{WINDOW_KEY}']"))[:, slot])

    lm.retire(session, [1])
    before = ring(1), ring(0)
    lm.step(session, np.asarray([5, 6, 0, 0], np.int32))
    assert (ring(1) == before[0]).all() and not (ring(0) == before[1]).all()


# ----------------------------------------------------------- the rungs' read

# eight slots at a table of 4 096 (chunks of 512: the walk's ladder is 1, 2, 4,
# 8 rows): prompts past a wrap of the ring of 24 (30, 47, 60), one a step short
# of it (23: it wraps under the steps), rows shorter than the window of 8 (5,
# 3), one exactly as long, one between
RUNG_LENS = np.asarray([30, 5, 47, 11, 3, 23, 8, 60])
RUNG_STEPS = 3
RUNG_IDS = np.random.RandomState(6).randint(1, 128, (8, 64)).astype(np.int32)
LIVE_ROWS = {
    "1_row": [5], "2_rows": [1, 4], "3_rows_of_rung_4": [0, 5, 7], "4_rows": [0, 2, 4, 6],
    "7_rows_of_rung_8": [0, 1, 2, 4, 5, 6, 7], "8_rows": list(range(8)),
}


@pytest.fixture(scope="module")
def rung_lm(params):
    world()
    return serving_lm(params, cfg=LagunaConfig(**dict(TINY, max_seq_len=4096)), buckets=(64,),
                      max_batch=8).compile()


@pytest.fixture(scope="module")
def rung_want(params):
    return np.asarray(reference.forward(params, jnp.asarray(RUNG_IDS), SIZES))


@pytest.mark.parametrize("case", sorted(LIVE_ROWS))
def test_every_rung_reads_its_rows_windows_and_writes_no_dead_rows_ring(
        rung_lm, rung_want, case):
    """One-token steps with 1, 2, 3, 4, 7 and 8 of 8 rows live, dead rows
    between the live ones: each live row's logits are the full-attention
    reference's, masked to the window (the rows of a rung are sorted by reach,
    taken out of the stacked head-major leaf by slices and put back), and a
    dead row's rings keep every byte in every window layer."""
    world()
    live = LIVE_ROWS[case]
    dead = sorted(set(range(8)) - set(live))
    session = rung_lm.start_session()
    rung_lm.insert(session, np.arange(8), padded(RUNG_IDS, range(8), RUNG_LENS),
                   lengths=RUNG_LENS, reserve_tokens=RUNG_STEPS + 2)
    if dead:
        rung_lm.retire(session, dead)

    def rings():
        return [np.asarray(leaf) for path, leaf in
                jax.tree_util.tree_flatten_with_path(session.cache)[0]
                if "window_" in jax.tree_util.keystr(path)]

    before = rings()
    assert len(before) == 2 and before[0].shape == (3, 8, 2, RING, 8)
    scale = np.abs(rung_want).max()
    for t in range(RUNG_STEPS):
        tok = np.zeros((8,), np.int32)
        tok[live] = [RUNG_IDS[r, RUNG_LENS[r] + t] for r in live]
        got = np.asarray(rung_lm.step(session, tok))
        for r in live:
            assert distance(got[r], rung_want[r, RUNG_LENS[r] + t], scale) <= TOL, (r, t)
    for was, now in zip(before, rings()):
        assert (now[:, dead] == was[:, dead]).all()
        for r in live:      # RUNG_STEPS new tokens a layer, each at its position % RING
            changed = np.nonzero((now[:, r] != was[:, r]).any(axis=(0, 1, 3)))[0]
            assert sorted(changed) == sorted((RUNG_LENS[r] + t) % RING for t in range(RUNG_STEPS))


# ----------------------------------------------------------- planted faults

FAULTS = {
    "window_ignored": dict(sliding_window=10 ** 6),
    "window_one_short": dict(sliding_window=WINDOW - 1),
    "window_one_long": dict(sliding_window=WINDOW + 1),
    "gate_dropped": dict(gating="none"),
    "full_layer_rope_on_a_sliding_layer": dict(
        rope_parameters={FULL: ROPE[FULL], SLIDING: ROPE[FULL]}),
    "softmax_for_sigmoid": dict(scoring_func="softmax"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_wrong_mathematics_fails_the_same_tolerance(served, params, fault):
    """Each planted in the reference's sizes and held against what the
    program served: the two must part by far more than rounding."""
    _, first, steps = served
    wrong = np.asarray(reference.forward(params, jnp.asarray(IDS[:2]),
                                         dict(SIZES, **FAULTS[fault])))
    scale = np.abs(wrong).max()
    gap = max(distance(steps[:8], reference_steps(wrong, 0, 8), scale),
              max(distance(first[r], wrong[r, LENS[r] - 1], scale) for r in (0, 1)))
    assert gap > 50 * TOL, gap


# ------------------------------------------------------------ the expert layer

def test_the_shares_add_up_to_the_uncut_layer(params):
    """Eight "chips" hold experts 2g, 2g + 1 of one layer's 16. The routed
    parts they compute (serving's grouped path, a share told what it holds)
    plus the shared expert, counted once, are what the uncut reference gives."""
    world()
    rng = np.random.RandomState(2)
    z = rng.normal(size=(2, 10, 32)).astype(np.float32)
    gate, up, down = (rng.normal(size=s).astype(np.float32) * 0.2
                      for s in ((16, 32, 16), (16, 32, 16), (16, 16, 32)))
    router = rng.normal(size=(32, 16)).astype(np.float32)
    shared = jax.tree.map(lambda a: a[0],
                          params["model"]["periods"][f"{SLIDING}_0"]["shared_expert"])

    def share(first, held):
        moe = MoE(num_experts=held, hidden_size=32, intermediate_size=16, top_k=3,
                  norm_topk_prob=True, dtype=jnp.float32, inference=True,
                  router_experts=None if held == 16 else 16, experts_held_first=first,
                  route_scale=2.5, scoring_func="sigmoid")
        tree = {"router": {"kernel": router},
                "experts": {k: w[first: first + held] for k, w in
                            (("gate", gate), ("up", up), ("down", down))}}
        with jax.default_matmul_precision("highest"):
            return np.asarray(moe.apply({"params": tree}, jnp.asarray(z)))

    with jax.default_matmul_precision("highest"):
        combine = reference.route(jnp.asarray(z), router, 3, "sigmoid", True, 2.5)
        once = reference._mlp_add(jnp.zeros_like(z), jnp.asarray(z), shared)
        uncut = once
        for e in range(16):
            uncut = reference._expert_add(uncut, jnp.asarray(z), combine[..., e], gate[e], up[e],
                                          down[e])
    parts = [share(first, 2) for first in range(0, 16, 2)]
    assert sum(np.abs(p).max() > 0 for p in parts) >= 6            # nearly every chip had work
    assert distance(sum(parts) + np.asarray(once), np.asarray(uncut)) <= TOL
    assert distance(share(0, 16) + np.asarray(once), np.asarray(uncut)) <= TOL


def parents_router(x, w, top_k, norm_topk_prob, n_group, topk_group, route_scale):
    """``RouterTopK.__call__`` as the parent commit had it, letter for letter."""
    num_experts = w.shape[1]
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    eligible = probs
    if n_group > 1:
        eligible = probs * group_limit(probs, n_group, topk_group)
    topv, topi = jax.lax.top_k(eligible, top_k)
    mask = jnp.sum(jax.nn.one_hot(topi, num_experts, dtype=probs.dtype), axis=-2)
    gates = probs * mask
    if norm_topk_prob:
        denom = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / jnp.maximum(denom, 1e-9)
    if route_scale != 1.0:
        gates = gates * route_scale
    return gates, logits


ROUTERS = {
    "mixtral": dict(num_experts=8, top_k=2),
    "olmoe": dict(num_experts=64, top_k=8, norm_topk_prob=False),
    "deepseek_v2": dict(num_experts=160, top_k=6, norm_topk_prob=False, n_group=8, topk_group=3,
                        route_scale=16.0),
}


@pytest.mark.parametrize("model", sorted(ROUTERS))
def test_the_softmax_router_is_bit_equal_to_the_parents(model):
    kw = ROUTERS[model]
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(size=(24, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, kw["num_experts"])).astype(np.float32))
    got = [RouterTopK(**dict(kw, **extra)).apply({"params": {"kernel": w}}, x)
           for extra in ({}, {"scoring_func": "softmax"})]
    want = parents_router(x, w, kw["top_k"], kw.get("norm_topk_prob", True),
                          kw.get("n_group", 1), kw.get("topk_group", 1),
                          kw.get("route_scale", 1.0))
    for gates, logits in got:
        assert (np.asarray(gates) == np.asarray(want[0])).all()
        assert (np.asarray(logits) == np.asarray(want[1])).all()


def test_the_sigmoid_router_by_hand():
    """Top 2 of 4 by sigmoid, renormalised, times 2.5; an unknown score raises."""
    w = jnp.eye(4, dtype=jnp.float32)
    x = jnp.asarray([[2.0, -1.0, 0.5, 0.0]], jnp.float32)
    gates, _ = RouterTopK(4, top_k=2, route_scale=2.5, scoring_func="sigmoid").apply(
        {"params": {"kernel": w}}, x)
    s = 1 / (1 + np.exp(-np.asarray([2.0, 0.5])))
    np.testing.assert_allclose(np.asarray(gates)[0], [2.5 * s[0] / s.sum(), 0, 2.5 * s[1] / s.sum(), 0],
                               rtol=1e-6)
    with pytest.raises(ValueError, match="scoring_func"):
        RouterTopK(4, scoring_func="tanh").apply({"params": {"kernel": w}}, x)


# ------------------------------------------------------------------ the cache

def test_the_leaves_are_stacked_by_kind_and_a_ring_does_not_grow_with_the_table(params, lm):
    world()
    shapes = {jax.tree_util.keystr(p).split("']['")[-1].strip("']"): leaf.shape
              for p, leaf in jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]}
    pages = lm.config.page_pool_pages
    assert shapes["cached_key"] == shapes["cached_value"] == (2, pages, 16, 2, 8)
    assert shapes["window_key"] == shapes["window_value"] == (3, 4, 2, RING, 8)
    sizes = lm.kv_cache_bytes()
    assert sizes["kv_bytes"] == 2 * 2 * pages * 16 * 2 * 8 * 4
    assert sizes["window_bytes"] == 2 * 3 * 4 * RING * 2 * 8 * 4 and "state_bytes" not in sizes
    assert lm.slot_rows == ("window_key", "window_value") and lm.wants_live
    assert not lm.slot_rows_continue and lm.walk_sums == 5 and not lm.scans
    longer = serving_lm(params, cfg=LagunaConfig(**dict(TINY, max_seq_len=512))).kv_cache_bytes()
    assert longer["window_bytes"] == sizes["window_bytes"] and longer["kv_bytes"] > sizes["kv_bytes"]


def test_the_published_sizes_hold_a_ring_of_528_and_the_reckoned_parameters():
    cfg = laguna_s_2_1(num_layers=9, num_experts=32, router_experts=256, page_size=16,
                       page_pool_pages=8 * 513)
    assert (cfg.period, cfg.ring, cfg.layers_of(FULL), cfg.layers_of(SLIDING)) == (4, 528, 3, 6)
    assert cfg.layer_types == (FULL, SLIDING, SLIDING, SLIDING) * 2 + (FULL,)
    leaves = cfg.kv_leaf_shapes(8)
    assert leaves["window_key"][0] == (8, 8, 528, 128)      # head-major: (slots, n_kv, ring, hd)
    assert leaves["cached_key"][0] == (8 * 513, 16, 8, 128)
    full, window = cfg.of_kind(FULL), cfg.of_kind(SLIDING)
    assert (full.num_heads, full.rope_dims, full.sliding_window) == (48, 64, None)
    assert (window.num_heads, window.rope_dims, window.sliding_window) == (72, 128, 512)
    assert full.rope_scaling.attention_factor == 1.4852030263919618 and window.rope_scaling is None
    assert full.rope_scaling.frequencies(64, full.rope_theta)[1] == 1.4852030263919618


def test_the_window_counters_by_hand(lm):
    """Two rows, prompts of 5 and 12, four tokens each: ONE fused block of 8
    steps in which both are live (the budget is the host's: the device steps
    a row until the block ends). Every step reads the rings of its rung (the
    batch's 4 rows here: a table of one chunk has one rung) in the 3 window
    layers; it needed ``min(reach, 8)`` tokens a row a layer, reach = prompt
    + step."""
    world()
    engine = ServeEngine(lm, rng=jax.random.key(1))
    for n in (5, 12):
        engine.submit(IDS[2, :n], max_new_tokens=4, arrival_block=engine.blocks)
    while engine.step_block():
        pass
    assert sorted(len(c.tokens) for c in engine.completed) == [4, 4]
    steps = engine.block_steps
    assert steps == 8 and engine.stats["kv_walk_steps"] == steps
    assert engine.stats["kv_window_slots_read"] == 3 * steps * 4 * RING
    needed = 3 * sum(min(n + t, WINDOW) for n in (5, 12) for t in range(1, steps + 1))
    assert engine.stats["kv_window_slots_needed"] == needed
    # the full layers' counters count the full layers' walk alone
    assert engine.stats["kv_walk_tokens"] == steps * 64


def test_the_rung_holds_the_live_rows_at_a_long_table(params):
    """At 512 slots the walk has rungs (1 and 4 rows of 4): one live row reads
    ONE ring a window layer-step, and still agrees with the reference."""
    world()
    lm = serving_lm(params, cfg=LagunaConfig(**dict(TINY, max_seq_len=512)), buckets=(32,)).compile()
    engine = ServeEngine(lm, rng=jax.random.key(1))
    engine.submit(IDS[0, :20], max_new_tokens=12, arrival_block=engine.blocks)
    while engine.step_block():
        pass
    done = engine.completed[0]
    assert engine.stats["kv_window_slots_read"] == 3 * engine.stats["kv_walk_steps"] * 1 * RING
    full = np.concatenate([IDS[0, :20], np.asarray(done.tokens)])
    ref = np.asarray(reference.forward(params, jnp.asarray(full[None]), SIZES))[0]
    assert (ref[19:-1].argmax(-1) == np.asarray(done.tokens)).all()


# ------------------------------------------------------------------ refusals

REFUSED = {
    "prefix_cache": lambda p: serving_lm(p, prefix_cache=True),
    "the_slab": lambda p: serving_lm(p, page_size=None),
    "lora": lambda p: serving_lm(p, lora_rank=4, lora_slots=2),
    "int8_pages": lambda p: serving_lm(p, page_dtype="int8"),
    "handoff_prefill": lambda p: ServeEngine(serving_lm(p), role="prefill"),
    "handoff_decode": lambda p: ServeEngine(serving_lm(p), role="decode"),
    "host_tier": lambda p: ServeEngine(serving_lm(p), host_tier_pages=4),
    "parking": lambda p: ServeEngine(serving_lm(p), park_idle_blocks=2, park_dir="/nonexistent"),
    "page_corruption": lambda p: ServeEngine(serving_lm(p)).inject_page_corruption([1]),
    "generate": lambda p: serving_lm(p).generate(IDS[:1, :8], 4),
    "chunked_prefill": lambda p: ServeEngine(serving_lm(p), prefill_chunk_tokens=16),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_moves_or_continues_a_slots_cache_by_pages_is_refused(params, feature):
    world()
    with pytest.raises(ValueError):
        REFUSED[feature](params)


def test_an_extend_that_continues_a_row_is_refused(lm, served):
    world()
    session, _, _ = served
    with pytest.raises(ValueError, match="continues a row"):
        lm.extend(session, np.asarray([1]), IDS[1:2, :4], np.asarray([4]), np.asarray([40]),
                  tables=np.zeros((1, 4), np.int32))


def test_the_ring_leaves_are_not_served_across_tp(params, monkeypatch):
    assert leaf_partition_spec("['model']['window_key']", (6, 8, 8, 528, 128), 4) == PartitionSpec()
    world()
    monkeypatch.setattr(causal_lm, "tp_degree", lambda: 4)
    with pytest.raises(ValueError, match="tensor parallelism"):
        serving_lm(params)


def test_the_generic_decode_path_refuses_a_window(params):
    """``LlamaAttention`` masks a forward pass by ``sliding_window`` and
    refuses to CACHE under one: the ring is ``LagunaAttention``'s."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    world()
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=1,
                      num_heads=4, num_kv_heads=2, max_seq_len=64, dtype=jnp.float32,
                      sliding_window=8, use_flash_attention=False, remat_policy=None)
    weights = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    with pytest.raises(ValueError, match="ring a slot"):
        CausalLM(cfg, weights, LlamaForCausalLM, buckets=(16,), max_batch=2, page_size=16).compile()


@pytest.mark.parametrize("bad", [
    dict(layer_types=[FULL] * 3), dict(layer_types=[FULL, SLIDING, "linear", SLIDING, FULL]),
    dict(num_heads_per_layer=[4, 6, 4, 6, 4]), dict(mlp_only_layers=[1]),
    dict(layer_types=[SLIDING] + TYPES[1:]), dict(sliding_window=None),
    dict(layer_types=[FULL, SLIDING, SLIDING, FULL, SLIDING],
         num_heads_per_layer=[4, 6, 6, 4, 6]), dict(experts_held_first=12),
    dict(rope_parameters={FULL: dict(rope_type="llama3")}),
], ids=["length", "kinds", "heads", "dense_layer", "first_layer", "no_window", "period",
        "share", "rope"])
def test_a_configuration_the_model_cannot_run_is_refused(bad):
    with pytest.raises(ValueError):
        LagunaConfig(**dict(TINY, **bad))


def test_the_kinds_view_keeps_what_it_does_not_change():
    cfg = LagunaConfig(**TINY)
    view = cfg.of_kind(SLIDING)
    changed = {"kind", "num_heads", "rope_theta", "rope_scaling"}
    for f in dataclasses.fields(cfg):
        if f.name not in changed:
            assert getattr(view, f.name) == getattr(cfg, f.name), f.name
    assert cfg.of_kind(FULL).sliding_window is None and hash(view) != hash(cfg.of_kind(FULL))


# ----------------------------------------------------- the windowed flash call

def _qkv(b=2, h=4, hk=2, s=256, d=16, seed=4):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, n, s, d)).astype(np.float32))
                 for n in (h, hk, hk))


def dense_mask_attention(q, k, v, q_pos, kv_pos, window):
    """softmax(q k / sqrt(d)) v under a dense (s, s) mask, written out."""
    g = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, g, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    seen = (kv_pos[:, None, :] <= q_pos[:, :, None]) & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
    scores = jnp.where(seen[:, None], scores, -jnp.inf)
    probs = jnp.where(seen.any(-1)[:, None, :, None], jax.nn.softmax(scores, axis=-1), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("window,block_q,block_k", [
    (1, 64, 64), (8, 64, 64), (64, 64, 64), (65, 128, 64), (100, 32, 128), (256, 64, 64),
    (1000, 128, 128)])
def test_the_windowed_flash_call_is_the_dense_mask(window, block_q, block_k):
    from neuronx_distributed_tpu.kernels.flash_attn import flash_attention

    q, k, v = _qkv()
    pos = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (2, 256))
    with jax.default_matmul_precision("highest"):
        got = flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                              window=window)
        want = dense_mask_attention(q, k, v, pos, pos, window)
    assert distance(got, want) <= 1e-5


def test_the_windowed_flash_call_takes_ragged_positions():
    """Queries at a row's own offset, keys with unwritten slots marked, pad
    query rows at -1: the window is taken in POSITIONS, not in places."""
    from neuronx_distributed_tpu.kernels.flash_attn import INVALID_POS, flash_attention
    from neuronx_distributed_tpu.ops.attention import attention

    q, k, v = _qkv(s=128)
    q = q[:, :, :64]
    kv_pos = jnp.stack([jnp.arange(128), jnp.where(jnp.arange(128) < 90, jnp.arange(128),
                                                   INVALID_POS)]).astype(jnp.int32)
    q_pos = jnp.stack([40 + jnp.arange(64), jnp.where(jnp.arange(64) < 50, 26 + jnp.arange(64),
                                                      -1)]).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = dense_mask_attention(q, k, v, q_pos, kv_pos, 24)
        for fn in (flash_attention, attention):     # the kernel, and the dispatch above it
            got = fn(q, k, v, causal=False, block_q=32, block_k=32, q_positions=q_pos,
                     kv_positions=kv_pos, window=24)
            assert distance(got, want) <= 1e-5
        plain = attention(q, k, v, causal=False, use_flash=False, q_positions=q_pos,
                          kv_positions=kv_pos, window=24)
    assert distance(plain, want) <= 1e-5


def test_without_a_window_the_flash_call_lowers_to_what_it_was():
    """``window=None`` passes nothing on: the kernel keeps its name and its
    text; a window changes both; a windowed call cannot be differentiated."""
    from neuronx_distributed_tpu.kernels.flash_attn import flash_attention

    q, k, v = _qkv(s=128)

    def text(**kw):
        return jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=64, **kw)
                       ).lower(q, k, v).as_text()

    assert text() == text(window=None)
    assert text(window=16) != text()
    jaxpr = str(jax.make_jaxpr(lambda q: flash_attention(q, k, v, block_q=64, block_k=64))(q))
    assert "flash_fwd" in jaxpr and "flash_fwd_window" not in jaxpr
    assert "flash_fwd_window" in str(jax.make_jaxpr(
        lambda q: flash_attention(q, k, v, block_q=64, block_k=64, window=16))(q))
    jax.grad(lambda q: flash_attention(q, k, v, block_q=64, block_k=64).sum())(q)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(q, k, v, block_q=64, block_k=64, window=16).sum())(q)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64, window=0)


def test_a_forward_pass_masks_by_the_window_through_the_flash_kernel(params):
    """The model outside decode mode (what initialises it) runs the window
    layers through ``flash_attention(window=)``, interpreted here. Its experts
    drop by capacity, so this one takes all_experts over a router cut to the
    8 experts held, and the reference is told the same."""
    world()
    cfg = LagunaConfig(**dict(TINY, use_flash_attention=True, moe_mode="all_experts",
                              router_experts=None, num_experts=8))
    held = jax.tree_util.tree_map_with_path(
        lambda path, a: a[..., :8] if "router" in jax.tree_util.keystr(path) else a, params)
    ref = np.asarray(reference.forward(held, jnp.asarray(IDS[:2, :32]), SIZES))
    got = LagunaForCausalLM(cfg).apply({"params": held}, jnp.asarray(IDS[:2, :32]))
    assert distance(got, ref) <= TOL


# the lowered text of a tiny Llama paged insert (flash kernel, pages, table
# write, first token), hashed on PR 49's PARENT commit: `window`, the gate and
# the partial rotation are Python branches a config without them never takes.
# (PR 49 also hashed 49 serving programs and 22 forward / loss-gradient
# lowerings of every rehearsal configuration on both trees: PERF.md section 6.)
# A PR that changes what an insert holds on purpose re-pins this.
PARENTS_INSERT = "0e6b9f5f71b651a9ca2206ebb215a8e3f3d2dab5"


def test_a_config_without_a_window_lowers_its_insert_to_the_parents_text():
    import hashlib

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    world()
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=256, dtype=jnp.float32,
                      use_flash_attention=True, remat_policy=None)
    # eager, as when the text was taken: the lowered arguments carry the
    # shardings an eager ``unbox`` pins on the scanned leaves (two dense layers: 2 s)
    weights = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    lm = CausalLM(cfg, weights, LlamaForCausalLM, buckets=(128,), max_batch=2, page_size=16)
    texts = []
    real = jax.stages.Lowered.compile

    def keep(self, *a, **k):
        texts.append(self.as_text())
        return real(self, *a, **k)

    jax.stages.Lowered.compile = keep
    try:
        lm._paged_insert_programs(2, 128)
    finally:
        jax.stages.Lowered.compile = real
    assert len(texts) == 1 and "flash_fwd_window" not in texts[0]
    assert hashlib.sha1(texts[0].encode()).hexdigest() == PARENTS_INSERT


def test_a_long_insert_hands_on_the_held_picks_alone():
    """Four of sixteen experts held, top-3, a prompt of 200 tokens in a bucket
    of 256: the expert layers' lists are 768 picks long and a pass takes 384
    of them (``row_bound``). The last position's logits are the reference's,
    and the insert's sums say what the passes handled: one a layer call here,
    so ``moe_insert_rows`` is layer calls x 384 and not x 768."""
    from neuronx_distributed_tpu.moe.expert_mlps import row_bound

    world()
    cfg = dict(TINY, num_experts=4, max_seq_len=512)
    ids = np.random.RandomState(3).randint(1, 128, (1, 200)).astype(np.int32)
    tree = tiny.make_params(LagunaForCausalLM, LagunaConfig(**cfg), seed=2)
    lm = CausalLM(LagunaConfig(**cfg), tree, LagunaForCausalLM, buckets=(256,), max_batch=2,
                  page_size=16, prefix_cache=False)
    session = lm.start_session()
    got = np.asarray(lm.insert(session, np.arange(1), ids, lengths=np.asarray([200]),
                               reserve_tokens=8))
    ref = np.asarray(reference.forward(tree, jnp.asarray(ids), SIZES))[0, -1]
    assert distance(got[0], ref) <= TOL
    touched, assigned, calls, routed, rows, multiplied, passes = (
        int(v) for v in np.asarray(session.insert_routing))
    bound = row_bound(256, 3, 4, 16)
    assert bound == 384 and calls == 4                 # the four layers with experts
    assert routed == 4 * 200 * 3 and 0 < assigned < routed
    assert passes == calls and rows == passes * bound
    assert assigned <= multiplied <= rows


@pytest.mark.parametrize("tokens,slice_tokens,calls", [
    (40, 16, [16, 16, 8]), (1300, 512, [512, 512, 512])], ids=["one_tile", "compact_passes"])
def test_a_long_grouped_call_goes_by_slices_and_adds_up(monkeypatch, tokens, slice_tokens, calls):
    """More tokens than ``GROUPED_TOKENS`` (an 8 x 4096 insert is 32 768) run
    the sort, the gather and the kernels a slice at a time: the same rows
    through the same experts. Slices of a tile's picks are the whole list
    each; longer ones (512 tokens: 1 536 picks, 4 of 16 experts held) hand on
    half their list a pass (``row_bound``), and so does the call taken whole
    (a class of 2 048 tokens)."""
    from neuronx_distributed_tpu.moe import expert_mlps

    world()
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(size=(1, tokens, 32)).astype(np.float32))
    moe = MoE(num_experts=4, hidden_size=32, intermediate_size=16, top_k=3, dtype=jnp.float32,
              inference=True, router_experts=16, experts_held_first=4, route_scale=2.5,
              scoring_func="sigmoid")
    tree = moe.init(jax.random.PRNGKey(1), x)["params"]
    live = jnp.asarray(rng.rand(1, tokens) < 0.8)
    whole = np.asarray(moe.apply({"params": tree}, x, live))
    monkeypatch.setattr(expert_mlps, "GROUPED_TOKENS", slice_tokens)
    seen = []
    real = expert_mlps._grouped_experts
    monkeypatch.setattr(expert_mlps, "_grouped_experts",
                        lambda x, *a, **k: seen.append(x.shape[0]) or real(x, *a, **k))
    sliced = np.asarray(moe.apply({"params": tree}, x, live))
    assert seen == calls and np.abs(whole).max() > 0
    assert distance(sliced, whole) <= 1e-6
    compact = [expert_mlps.row_bound(n, 3, 4, 16) < n * 3 for n in calls]
    assert compact == [tokens > 40] * 3
