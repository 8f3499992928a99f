"""DeepSeek-V2 (``models/deepseek_v2.py``) against its plain reference, tiny
widths, float32, logits and not tokens.

128 heads of latent attention become 4 (latent 32 + 8 rotary, q rank 48), 160
routed experts in 8 groups become 16 in 4 of which 2 are kept, top-6 becomes
top-4, the leading dense layer is followed by two expert layers, and a "chip"
holds 4 of the 16 experts. Weights are seeded random; the norm scales are
shaken away from one so that a scale applied to the wrong axis shows.

The tolerance: float32 against float32 under ``highest`` matmul precision, so
only the order of additions differs (the absorbed decode multiplies ``W_uk``
into the query instead of into the keys; the grouped matmul adds a token's
experts in another order): logits within 1e-4 of the reference's largest. The
right mathematics sits near 1e-6; the reference with its weights rounded to
bf16 is over 500 times past the tolerance (``test_a_lower_precision_fails``).
"""

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import deepseek_v2 as reference
from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
from neuronx_distributed_tpu.inference.partition import leaf_partition_spec
from neuronx_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2Attention,
    DeepseekV2Config,
    DeepseekV2ForCausalLM,
    deepseek_v2,
)
from neuronx_distributed_tpu.models.llama import KVLayerView, YarnScaling
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.moe.routing import RouterTopK
from tests import tiny
from tests.tiny import IDS, LENS, STEPS, at_cached, cached_logits, distance, world

TOL = 1e-4
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3, num_heads=4,
            num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, first_k_dense=1, moe_intermediate_size=32,
            n_shared_experts=2, router_experts=16, num_experts=4, experts_held_first=4,
            n_group=4, topk_group=2, top_k=4, rope_scaling=YARN, max_seq_len=64,
            dtype=jnp.float32, param_dtype=jnp.float32, use_flash_attention=False,
            remat_policy=None, moe_mode="all_experts")
SIZES = {"rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": YARN,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 16.0,
         "norm_topk_prob": False, "experts_held_first": 4, "router_experts": 16}
full_forward = functools.partial(tiny.full_forward, DeepseekV2ForCausalLM)
serving_lm = functools.partial(tiny.serving_lm, DeepseekV2ForCausalLM, cfg=DeepseekV2Config(**TINY))


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(DeepseekV2ForCausalLM, DeepseekV2Config(**TINY), IDS, tiny.shake_norms)


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))


def test_preset_is_the_published_configuration():
    cfg = deepseek_v2()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (60, 5120, 128, 102400)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.latent_dim, cfg.head_dim_) == (512, 1536, 576, 192)
    assert (cfg.num_experts, cfg.top_k, cfg.n_group, cfg.topk_group) == (160, 6, 8, 3)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)


# --------------------------------------------------------------- the forward

@pytest.mark.parametrize("held", ["share", "all"])
def test_full_forward_equals_the_reference(params, want, held):
    world()
    if held == "share":
        assert distance(full_forward(DeepseekV2Config(**TINY), params), want) <= TOL
        return
    cfg = DeepseekV2Config(**dict(TINY, num_experts=16, experts_held_first=0))
    uncut = tiny.make_params(DeepseekV2ForCausalLM, cfg, IDS, tiny.shake_norms)
    sizes = dict(SIZES, experts_held_first=0)
    assert distance(full_forward(cfg, uncut),
                    reference.forward(uncut, jnp.asarray(IDS), sizes)) <= TOL


@pytest.mark.parametrize("wrong", [dict(topk_group=4), dict(routed_scaling_factor=1.0),
                                   dict(n_shared_experts=0), dict(rope_scaling=None),
                                   dict(experts_held_first=8)],
                         ids=lambda w: next(iter(w)))
def test_wrong_mathematics_fails(params, want, wrong):
    """No group limit, no route scale, no shared expert, plain rope, another
    chip's experts: each moves the logits far past the tolerance."""
    world()
    cfg = DeepseekV2Config(**dict(TINY, **wrong))
    tree = params
    if "n_shared_experts" in wrong:
        block = dict(params["model"]["layers"]["block"])
        block.pop("shared_expert")
        tree = {**params, "model": {**params["model"], "layers": {"block": block}}}
    assert distance(full_forward(cfg, tree), want) > 10 * TOL


def test_a_lower_precision_fails(params, want):
    """The control: the reference itself with every weight rounded to bf16
    lands far outside the float32 tolerance, so the comparison would see a
    program that computed in less than it states."""
    rounded = jax.tree.map(lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32), params)
    low = np.asarray(reference.forward(rounded, jnp.asarray(IDS), SIZES))
    assert distance(low, want) > 10 * TOL


def test_yarn_frequencies_equal_the_references():
    scaling = YarnScaling(**{k: v for k, v in YARN.items() if k != "type"})
    inv, amplitude = scaling.frequencies(8, 10000.0)
    np.testing.assert_allclose(inv, reference.yarn_inv_freq(8, 10000.0, YARN), rtol=1e-6)
    assert amplitude == pytest.approx(1.0)
    # the published ramp at the published sizes: dims 10..23 of 32 blend
    pub = dict(YARN, original_max_position_embeddings=4096)
    inv = reference.yarn_inv_freq(64, 10000.0, pub)
    own = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], own[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[24:], own[24:] / 40, rtol=1e-6)
    assert ((inv[11:23] < own[11:23]) & (inv[11:23] > own[11:23] / 40)).all()


# ------------------------------------------------------------- the serving path

@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_insert_and_decode_through_the_latent_cache_equal_the_reference(params, want, cache):
    """Prefill in the expanded form, then every decoded position in the
    absorbed form over the cached latent, against the reference's full forward
    (which never takes the absorbed form)."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params, cache)
    assert distance(cached_logits(lm), at_cached(want)) <= TOL


def test_absorbed_decode_equals_expanded_attention_on_the_same_latent():
    """One attention block over a slab it owns: seven tokens at once (expanded)
    then one more (absorbed) gives, at the eighth position, what eight at once
    (expanded) give; and the two runs leave the same latent behind."""
    cfg = dataclasses.replace(DeepseekV2Config(**TINY), decode=True, num_layers=1)
    attn = DeepseekV2Attention(cfg)
    x = jax.random.normal(jax.random.key(3), (2, 8, cfg.hidden_size), jnp.float32)

    def run(variables, x, leaves):
        view = KVLayerView(jnp.int32(0), leaves)
        out, mut = attn.apply(variables, x, None, kv=view, mutable=["cache"])
        return out, mut["cache"], view.leaves

    empty = {"cached_key": jnp.zeros((1, 2, cfg.max_seq_len, 1, cfg.latent_dim), jnp.float32)}
    init = attn.init(jax.random.key(0), x, None, kv=KVLayerView(jnp.int32(0), empty))
    weights = {"params": meta.unbox(init["params"])}
    with jax.default_matmul_precision("highest"):
        whole, _, left_whole = run(weights, x, empty)
        _, cache, leaves = run(weights, x[:, :7], empty)
        last, cache, left_steps = run({**weights, "cache": cache}, x[:, 7:], leaves)
    assert int(cache["cache_index"][0]) == 8
    np.testing.assert_allclose(last[:, 0], whole[:, 7], rtol=0, atol=2e-6)
    np.testing.assert_allclose(left_steps["cached_key"], left_whole["cached_key"], rtol=0,
                               atol=1e-6)


def test_the_latent_leaf_is_one_leaf_and_counts_its_own_bytes(params):
    world()
    cfg = DeepseekV2Config(**dict(TINY, dtype=jnp.bfloat16))
    lm = serving_lm(params, cfg=cfg)
    leaves = {jax.tree_util.keystr(p): a for p, a in
              jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]}
    pools = {p: a for p, a in leaves.items() if "cached" in p}
    pages = lm.config.page_pool_pages
    assert list(pools) == ["['model']['cached_key']"]
    assert pools["['model']['cached_key']"].shape == (3, pages, 8, 1, 32 + 8)
    # the small per-layer leaves of both scans: (layers of the scan, rows)
    assert leaves["['model']['dense_layers']['block']['attention']['cache_index']"].shape == (1, 4)
    assert leaves["['model']['layers']['block']['attention']['cache_index']"].shape == (2, 4)
    per_token_layer = lm.kv_cache_bytes()["kv_bytes"] / (3 * pages * 8)
    assert per_token_layer == 2 * (32 + 8)


def test_int8_pages_are_refused_for_a_latent_leaf(params):
    world()
    with pytest.raises(ValueError, match="latent"):
        serving_lm(params, page_dtype="int8")


def test_a_one_head_leaf_is_replicated_under_tp():
    from jax.sharding import PartitionSpec

    latent = (6, 520, 16, 1, 576)
    assert leaf_partition_spec("['model']['cached_key']", latent, 4) == PartitionSpec()
    gqa = (6, 520, 16, 8, 128)
    assert leaf_partition_spec("['model']['cached_key']", gqa, 4) == PartitionSpec(
        None, None, None, "tp", None)


def test_serve_engine_gives_solo_generates_tokens_and_hits_a_latent_prefix(params):
    """Five requests, greedy, two of them sharing a 16-token prefix with an
    earlier one: each gets the tokens ``generate`` gives it alone, and the
    second sharer is admitted on the first's latent pages."""
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
    stats = engine.stats
    # the expert layers only, the held experts only, and every pick beside them
    assert stats["moe_layer_steps"] % 2 == 0 and stats["moe_layer_steps"] > 0
    assert stats["moe_experts_touched"] <= 4 * stats["moe_layer_steps"]
    assert 0 < stats["moe_assignments"] < stats["moe_assignments_routed"]
    assert stats["moe_assignments_routed"] % 4 == 0        # top-4 a live row and layer
    # the inserts' expert layers: lists of a tile, so one pass over the whole
    # list a call (``moe/expert_mlps.py::row_bound``)
    assert stats["moe_insert_passes"] == stats["moe_insert_layer_calls"] > 0
    assert 0 < stats["moe_insert_assignments"] < stats["moe_insert_rows"]


# --------------------------------------- a fresh insert reads its own tokens

def a_row_a_call(monkeypatch):
    """No two rows of a fresh insert fit one call: they go a row a call."""
    monkeypatch.setattr(sys.modules[DeepseekV2Attention.__module__], "PROMPT_CALL_BYTES", 0)


def prompt_block(cache, rows):
    """One attention block in decode mode over a latent leaf it owns (256 slots
    a row, paged 16 a page or slab-backed), 128 new tokens a row through the
    interpreted flash kernel: ``run(x, starts) -> (out, leaf)``."""
    paged = cache == "paged"
    cfg = dataclasses.replace(
        DeepseekV2Config(**TINY), decode=True, num_layers=1, max_seq_len=256,
        use_flash_attention=True,
        **(dict(page_size=16, page_pool_pages=16 * rows + 1) if paged else {}))
    attn = DeepseekV2Attention(cfg)
    shape = (1, 16 * rows + 1, 16, 1, cfg.latent_dim) if paged else (1, rows, 256, 1, cfg.latent_dim)
    leaves = {"cached_key": jnp.zeros(shape, jnp.float32)}
    x = jax.random.normal(jax.random.key(3), (rows, 128, cfg.hidden_size), jnp.float32)
    init = attn.init(jax.random.key(0), x, None, kv=KVLayerView(jnp.int32(0), leaves))
    weights = meta.unbox(init["params"])

    @jax.jit
    def run(x, starts):
        b = x.shape[0]
        cache = {"cache_index": starts}
        if paged:       # row i holds pages 1 + 16 i .. 16 + 16 i; page 0 is nobody's
            cache["block_table"] = 1 + jnp.arange(16 * b, dtype=jnp.int32).reshape(b, 16)
        view = KVLayerView(jnp.int32(0), leaves)
        with jax.default_matmul_precision("highest"):
            out, _ = attn.apply({"params": weights, "cache": cache}, x, None, kv=view,
                                mutable=["cache"])
        return out, view.leaves["cached_key"]

    return x, run


@pytest.mark.parametrize("cache", ["paged", "slab"])
@pytest.mark.parametrize("rows,call", [(1, "one"), (3, "one"), (4, "one"),
                                       (3, "a_row"), (4, "a_row")])
def test_a_fresh_insert_equals_the_slab_form_on_the_same_cache(cache, rows, call, monkeypatch):
    """``rows`` prompts that start at 0 attend over their own 128 tokens, all
    through one flash call or a row a call; the same rows beside ONE row that
    continues at slot 7 all read their 256-slot slabs back. Same outputs, same
    leaf."""
    if call == "a_row":
        a_row_a_call(monkeypatch)
    x, run = prompt_block(cache, rows + 1)
    fresh, left_fresh = run(x[:rows], jnp.zeros((rows,), jnp.int32))
    slab, left_slab = run(x, jnp.zeros((rows + 1,), jnp.int32).at[rows].set(7))
    np.testing.assert_allclose(fresh, slab[:rows], rtol=0, atol=2e-6)
    written = (slice(None), slice(1, 1 + 16 * rows)) if cache == "paged" else (
        slice(None), slice(0, rows))
    np.testing.assert_allclose(left_fresh[written], left_slab[written], rtol=0, atol=0)
    assert float(jnp.abs(left_fresh[written]).max()) > 0


@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_a_fresh_insert_a_row_a_call_equals_the_reference(params, want, cache, monkeypatch):
    """Three prompts of 18, 12 and 15 tokens in a bucket of 32 (the padding
    written and attended like tokens, its outputs never read), a row a call
    (the rule itself takes all three in one at these widths:
    ``test_insert_and_decode_through_the_latent_cache_equal_the_reference``):
    first-token logits inside the tolerance."""
    world()
    a_row_a_call(monkeypatch)
    with jax.default_matmul_precision("highest"):
        lm = serving_lm(params, page_size=8 if cache == "paged" else None).compile()
        session = lm.start_session()
        got = np.asarray(lm.insert(session, np.arange(3), IDS, lengths=LENS,
                                   **(dict(reserve_tokens=1) if lm.paged else {})))
    assert session.insert_fresh
    assert distance(got, want[np.arange(3), LENS - 1]) <= TOL


@pytest.mark.parametrize("heads,rows,tokens,one", [
    (32, 8, 512, True), (32, 1, 128, True),          # xing4.0-29b-a4b: .score, .chat
    (64, 8, 2048, False), (64, 5, 2048, False),      # longcat-flash-chat.longctx
    (128, 8, 2048, False), (128, 1, 2048, True)],    # deepseek-v2.longctx
    ids=["xing_8x512", "xing_1x128", "longcat_8x2048", "longcat_5x2048",
         "deepseek_8x2048", "deepseek_1x2048"])
def test_the_rows_of_a_fresh_insert_go_whole_where_the_calls_arrays_fit(heads, rows, tokens, one):
    """The rule at the latent cells' shapes (bf16, heads of 192): Xing's
    inserts are one flash call, the long-context cells' 2048-token rows a row
    a call as they always went, and one row is one call whatever its size."""
    cfg = dataclasses.replace(DeepseekV2Config(**TINY), num_heads=heads, num_kv_heads=heads,
                              qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                              dtype=jnp.bfloat16)
    assert cfg.head_dim_ == 192
    assert DeepseekV2Attention(cfg)._one_call(rows, tokens) is one


def test_an_insert_with_one_row_past_zero_reads_the_slab_and_equals_the_reference(params, want):
    """Two prompts in ONE insert, the first continuing a 16-token prefix that an
    earlier request left in the pool: the call is not fresh, every row reads its
    slab, and both rows' logits are the reference's."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params)
        session = lm.start_session()
        lm.insert(session, np.asarray([0]), IDS[:1, :18], lengths=np.asarray([18]),
                  reserve_tokens=1)
        assert session.insert_fresh
        lens = np.asarray([22, 15])
        got = np.asarray(lm.insert(session, np.asarray([1, 2]), IDS[:2, :22], lengths=lens,
                                   reserve_tokens=1))
    assert session.paged.stats["prefix_hits"] == 1 and not session.insert_fresh
    assert session.insert_ran == (22 - 16 + 15, 2 * 32)
    assert distance(got, want[np.arange(2), lens - 1]) <= TOL


@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_a_chunked_extend_after_a_fresh_insert_equals_the_one_shot_prefill(params, want, cache):
    """Ten tokens of two prompts inserted (fresh), the rest extended as one
    chunk that attends over what the insert left: logits at each row's last
    token are the whole prompt's."""
    world()
    lens, first = np.asarray([22, 17]), 10
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params, cache)
        session = lm.start_session()
        slots = np.arange(2)
        lm.insert(session, slots, IDS[:2, :first], lengths=np.full((2,), first),
                  **(dict(reserve_tokens=16) if lm.paged else {}))
        assert session.insert_fresh
        chunk = np.zeros((2, 12), np.int32)
        for i, n in enumerate(lens):
            chunk[i, : n - first] = IDS[i, first:n]
        tables = ({"tables": np.stack([session.paged.tables[i] for i in slots])}
                  if lm.paged else {})
        got = np.asarray(lm.extend(session, slots, chunk, lens - first,
                                   np.full((2,), first), **tables))
    assert not session.insert_fresh
    assert distance(got, want[np.arange(2), lens - 1]) <= TOL


# ------------------------------------------------------------------ the router

def loop_router(probs, n_group, topk_group, top_k):
    """The selection in a loop, one token at a time: (tokens, experts) 0/1."""
    T, E = probs.shape
    size = E // n_group
    chosen = np.zeros((T, E), bool)
    for t in range(T):
        score = [probs[t, g * size: (g + 1) * size].max() for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: (-score[g], g))[:topk_group]
        eligible = [e for e in range(E) if e // size in groups]
        for e in sorted(eligible, key=lambda e: (-probs[t, e], e))[:top_k]:
            chosen[t, e] = True
    return chosen


@pytest.mark.parametrize("case", ["random", "tie_between_groups", "a_strong_expert_outside"])
def test_group_limited_router_equals_a_loop(case):
    """16 experts in 4 groups, 2 groups kept, top-4. A tie between two groups
    goes to the lower one; an expert that the plain top-4 would take is left
    out when its group is not among the two best."""
    rng = np.random.RandomState(11)
    logits = rng.normal(size=(32, 16)).astype(np.float32)
    if case == "tie_between_groups":
        logits[:, :] = -1.0
        logits[:, [1, 6, 9]] = 2.0              # groups 0, 1 and 2 tie: 0 and 1 stay
        logits[:, [0, 4, 10, 11]] = 1.0
    elif case == "a_strong_expert_outside":
        logits[:, :] = -1.0
        logits[:, 0], logits[:, 5] = 3.0, 2.5   # the best of groups 0 and 1
        logits[:, 8] = 2.0                      # third group's best: above all that follow
        logits[:, [1, 2, 6]] = 1.0
    router = RouterTopK(16, top_k=4, norm_topk_prob=False, n_group=4, topk_group=2,
                        route_scale=16.0)
    weights = {"params": {"kernel": jnp.eye(16, dtype=jnp.float32)}}
    combine, _ = router.apply(weights, jnp.asarray(logits))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    chosen = loop_router(probs, 4, 2, 4)
    np.testing.assert_array_equal(np.asarray(combine) > 0, chosen)
    np.testing.assert_allclose(np.asarray(combine), 16.0 * probs * chosen, rtol=1e-6)
    if case == "tie_between_groups":
        assert chosen[0].nonzero()[0].tolist() == [0, 1, 4, 6]
    if case == "a_strong_expert_outside":
        assert not chosen[:, 8].any() and chosen[0].nonzero()[0].tolist() == [0, 1, 2, 5]


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four "chips" hold experts 0-3, 4-7, 8-11 and 12-15 of one layer's 16.
    The routed parts they compute (serving's grouped path, a share told what
    it holds) plus the shared expert, counted once, are what the uncut
    reference gives for the whole layer."""
    world()
    rng = np.random.RandomState(2)
    z = rng.normal(size=(2, 10, 64)).astype(np.float32)
    gate, up, down = (rng.normal(size=s).astype(np.float32) * 0.2
                      for s in ((16, 64, 32), (16, 64, 32), (16, 32, 64)))
    router = rng.normal(size=(64, 16)).astype(np.float32)
    shared = jax.tree.map(lambda a: a[0], params["model"]["layers"]["block"]["shared_expert"])

    def share(first, held):
        moe = MoE(num_experts=held, hidden_size=64, intermediate_size=32, top_k=4,
                  norm_topk_prob=False, dtype=jnp.float32, inference=True,
                  router_experts=None if held == 16 else 16, experts_held_first=first,
                  n_group=4, topk_group=2, route_scale=16.0)
        tree = {"router": {"kernel": router},
                "experts": {k: w[first: first + held] for k, w in
                            (("gate", gate), ("up", up), ("down", down))}}
        with jax.default_matmul_precision("highest"):
            return np.asarray(moe.apply({"params": tree}, jnp.asarray(z)))

    with jax.default_matmul_precision("highest"):
        combine = reference.route(jnp.asarray(z), router, 4, 4, 2, False, 16.0)
        uncut = reference._mlp_add(jnp.zeros_like(z), jnp.asarray(z), shared)
        for e in range(16):
            uncut = reference._expert_add(uncut, jnp.asarray(z), combine[..., e], gate[e], up[e],
                                          down[e])
        once = np.asarray(reference._mlp_add(jnp.zeros_like(z), jnp.asarray(z), shared))
    parts = [share(first, 4) for first in (0, 4, 8, 12)]
    assert all(np.abs(p).max() > 0 for p in parts)          # every chip had work
    assert distance(sum(parts) + once, np.asarray(uncut)) <= TOL
    assert distance(share(0, 16) + once, np.asarray(uncut)) <= TOL      # all held: today's path


# ------------------------------------------------- what the other models keep

def _tree(model):
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, OlmoeForCausalLM

    dense = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
                 num_heads=4, num_kv_heads=2, max_seq_len=64, dtype=jnp.float32)
    cfg, cls = {
        "mistral": (LlamaConfig(**dense), LlamaForCausalLM),
        "mixtral": (MixtralConfig(**dense, num_experts=4, top_k=2), MixtralForCausalLM),
        "olmoe": (OlmoeConfig(**dict(dense, num_kv_heads=4), num_experts=8, top_k=2),
                  OlmoeForCausalLM),
    }[model]
    params = meta.unbox(jax.eval_shape(
        lambda: cls(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))["params"]
    lm = CausalLM(cfg, params, cls, buckets=(32,), max_batch=2, page_size=8)
    flat = lambda tree: {jax.tree_util.keystr(p): a.shape for p, a in  # noqa: E731
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    return flat(params), flat(lm._cache_avals()), lm


@pytest.mark.parametrize("model", ["mistral", "mixtral", "olmoe"])
def test_a_model_without_leading_dense_layers_keeps_its_trees(model):
    """``first_k_dense == 0``: one scan named ``layers`` over all the layers,
    two pool leaves beside it, three routing sums."""
    world()
    params, cache, lm = _tree(model)
    assert not any("dense_layers" in p for p in [*params, *cache])
    assert params["['model']['layers']['block']['attention']['qkv']['q_kernel']"] == (2, 32, 4, 8)
    assert params["['model']['layers']['block']['input_norm']['scale']"] == (2, 32)
    ffn = ("['mlp']['gate_proj']['kernel']" if model == "mistral" else "['moe']['experts']['gate']")
    assert params["['model']['layers']['block']" + ffn][0] == 2
    n_kv = 4 if model == "olmoe" else 2
    pool = (2, lm.config.page_pool_pages, 8, n_kv, 8)
    assert cache["['model']['cached_key']"] == cache["['model']['cached_value']"] == pool
    assert cache["['model']['layers']['block']['attention']['cache_index']"] == (2, 2)
    assert lm.moe_sums == 3
