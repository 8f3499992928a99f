"""Xing4.0 (``models/xing4.py``, ``ops/stream_mix.py``) against its plain
reference, tiny widths, float32, logits and not tokens.

Four residual streams stay four and the Sinkhorn keeps its 20 turns; 64 sigmoid
experts at top-4 become 8 at top-2 with a NON-ZERO selection bias; hidden 3584
becomes 64. Weights are seeded random (the mix's own draw: ``phi`` at variance
1 / (nC), ``b_res`` 3 on the diagonal plus a unit normal); the norm scales and
the selection bias are shaken away from their neutral values so that a term
left out shows.

The tolerance: float32 against float32 under ``highest`` precision, 2e-5 of
the reference's largest logit (the right mathematics reads about 1e-6); every
planted fault of the mix reads a hundred times that or more.

No "the shares add up" test is owed: every routed expert is held, no share is taken.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4 as reference
from neuronx_distributed_tpu.inference import ServeEngine
from neuronx_distributed_tpu.models import xing4
from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Config, DeepseekV2ForCausalLM
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
from neuronx_distributed_tpu.models.longcat_flash import LongcatFlashConfig, LongcatFlashForCausalLM
from neuronx_distributed_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from neuronx_distributed_tpu.ops.stream_mix import StreamMix, sinkhorn_planes
from tests import tiny
from tests.tiny import IDS, at_cached, cached_logits, distance, world

TOL = 2e-5
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0}
LATENT = dict(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3, num_heads=4,
              num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32, max_seq_len=64,
              dtype=jnp.float32, param_dtype=jnp.float32, use_flash_attention=False,
              remat_policy=None)
TINY = dict(LATENT, first_k_dense=1, n_shared_experts=1, num_experts=8, top_k=2,
            rope_scaling=YARN, moe_mode="all_experts")
SIZES = {"rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": YARN,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts_per_tok": 2,
         "routed_scaling_factor": 2.0, "norm_topk_prob": True, "hc_mult": 4,
         "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
         "mhc_h_res_clamp_max": 30}
SUB_BLOCKS = 2 * 3          # stream mixes a token passes: two a layer, three layers
full_forward = functools.partial(tiny.full_forward, Xing4ForCausalLM)
serving_lm = functools.partial(tiny.serving_lm, Xing4ForCausalLM, cfg=Xing4Config(**TINY))


def shake(name, a):
    if "e_score_correction_bias" in name:
        return a + 0.1 * tiny.noise(name, a)
    return tiny.shake_norms(name, a)


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(Xing4ForCausalLM, Xing4Config(**TINY), IDS, shake)


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))


def test_preset_is_the_published_configuration():
    cfg = xing4.xing4_29b_a4b()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (40, 3584, 32, 131072)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max) == (-30.0, 30.0)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (768, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.n_group, cfg.topk_group, cfg.first_k_dense,
            cfg.n_shared_experts, cfg.moe_intermediate_size, cfg.intermediate_size) == \
        (64, 4, 1, 1, 2, 1, 1024, 9216)
    assert (cfg.scoring_func, cfg.router_selection_bias, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.router_experts) == ("sigmoid", True, True, 2.0, None)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2, rel=1e-6)
    assert cfg.stream_mixes == 80
    pool = dataclasses.replace(cfg, page_size=16, page_pool_pages=10).kv_leaf_shapes(8)
    assert {n: s for n, (s, _) in pool.items()} == {"cached_key": (10, 16, 1, 576)}


# --------------------------------------------------------------- the forward

def test_full_forward_equals_the_reference(params, want):
    world()
    bias = params["model"]["layers"]["block"]["moe"]["router"]["e_score_correction_bias"]
    assert np.abs(bias).max() > 0.05                    # the choice is not by the scores alone
    assert distance(full_forward(Xing4Config(**TINY), params), want) <= TOL


def test_the_parameter_tree_holds_a_mix_a_sub_block(params):
    for stack, layers in (("dense_layers", 1), ("layers", 2)):
        block = params["model"][stack]["block"]
        for mix in ("attn_mix", "ffn_mix"):
            assert {k: v.shape for k, v in block[mix].items()} == {
                "phi": (layers, 24, 4, 64), "alpha": (layers, 3), "beta": (layers, 24)}


MIX_COEFF, MIX_WRITE = reference.mix_coeff, reference.mix_write


def _transposed(x, y, post, res):
    return MIX_WRITE(x, y, post, jnp.swapaxes(res, -1, -2))


def _post_without_its_two(x, y, post, res):
    return MIX_WRITE(x, y, post / 2.0, res)


def _static_mix(x, mix, iters, eps, lo, hi):
    return MIX_COEFF(x, dict(mix, alpha=jnp.zeros((3,))), iters, eps, lo, hi)


def _route_without_its_scale(z, router, bias, top_k, renormalise, scale):
    return ROUTE(z, router, bias, top_k, renormalise, 1.0)


ROUTE, SINKHORN = reference.route, reference.sinkhorn
# planted in the REFERENCE: each is a mix (or a route) another reading of the
# config's keys could give, and the program must not agree with it
WRONG = {
    "h_res_transposed": ("mix_write", _transposed),
    "one_sinkhorn_iteration": ("sinkhorn", lambda m, iters, eps: SINKHORN(m, 1, eps)),
    "no_sinkhorn": ("sinkhorn", lambda m, iters, eps: m),
    "h_post_without_its_two": ("mix_write", _post_without_its_two),
    "a_static_mix": ("mix_coeff", _static_mix),
    "the_exit_is_stream_zero": ("exit_streams", lambda x: x[..., 0, :]),
    "no_route_scale": ("route", _route_without_its_scale),
}


@pytest.fixture(scope="module")
def got(params):
    world()
    return full_forward(Xing4Config(**TINY), params)


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_wrong_mathematics_fails(params, want, got, wrong, monkeypatch):
    """Each planted fault moves the reference's logits by a hundred
    tolerances or more: the program, which agrees with the sound reference,
    cannot agree with it."""
    name, planted = WRONG[wrong]
    monkeypatch.setattr(reference, name, planted)
    if name == "sinkhorn":
        # ``mix_coeff`` is jitted and looks ``sinkhorn`` up when TRACED: a jit of a
        # function made for this case traces anew, whatever the worker traced before
        monkeypatch.setattr(reference, "mix_coeff", jax.jit(
            lambda *args: MIX_COEFF.__wrapped__(*args), static_argnums=(2, 3, 4, 5)))
    faulty = np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))
    assert distance(faulty, want) > 100 * TOL
    assert distance(got, want) <= TOL < 100 * TOL < distance(got, faulty)


def test_a_lower_precision_fails(params, want):
    """bfloat16 weights (the nearest precision below float32 the program
    runs) read far over the tolerance: the comparison would see them."""
    world()
    low = jax.tree.map(lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)), params)
    assert distance(full_forward(Xing4Config(**TINY), low), want) > 100 * TOL


# ------------------------------------------------------------------- the mix

def streams(x):
    """(b, s, n, C), the reference's way, as the program's tuple of n (b, s, C)."""
    return tuple(x[:, :, i] for i in range(x.shape[2]))


def _mix_of(x, seed=0):
    mix = StreamMix(4, x.shape[-1], dtype=jnp.float32)
    variables = mix.init(jax.random.key(seed), streams(x), method="coeff")
    return mix, variables


def test_h_res_is_doubly_stochastic_after_twenty_turns_and_not_after_one():
    x = jax.random.normal(jax.random.key(3), (2, 5, 4, 64))
    mix, variables = _mix_of(x)
    pre, post, res = mix.apply(variables, streams(x), method="coeff")
    assert res.shape == (4, 4, 2, 5) and pre.shape == post.shape == (4, 2, 5)
    np.testing.assert_allclose(np.asarray(res.sum(1)), 1.0, atol=1e-5)      # rows came last
    start = jnp.exp(1.41 * jax.random.normal(jax.random.key(4), (4, 4, 64, 64))
                    + 3 * jnp.eye(4)[:, :, None, None])                    # the seeded draw's spread
    off = {turns: np.abs(np.asarray(sinkhorn_planes(start, turns, 1e-6).sum(0)) - 1.0)
           for turns in (1, 20)}                                            # the columns' sums
    # twenty turns of THIS draw (entries from e^-4 to e^7) leave the median
    # token's columns within 1e-3 of one (measured 2e-4) and the worst within
    # 0.05 (measured 0.033): the model takes exactly 20 and so does the program
    assert np.median(off[20]) < 1e-3 and off[20].max() < 0.05
    assert np.median(off[1]) > 0.05 and off[1].max() > 0.5                  # one turn does not
    # the reference's own loop, over (.., n, n), gives the same matrices
    theirs = reference.sinkhorn(jnp.moveaxis(start, (0, 1), (-2, -1)), 20, 1e-6)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(theirs, (-2, -1), (0, 1))),
                               np.asarray(sinkhorn_planes(start, 20, 1e-6)), atol=1e-6)


def test_the_mix_equals_the_references_piece_by_piece():
    x = jax.random.normal(jax.random.key(5), (2, 7, 4, 64))
    y = jax.random.normal(jax.random.key(6), (2, 7, 64))
    mix, variables = _mix_of(x, seed=2)
    pre, post, res = mix.apply(variables, streams(x), method="coeff")
    want = reference.mix_coeff(x, variables["params"], 20, 1e-6, -30.0, 30.0)
    for got, ref, axes in ((pre, want[0], (0,)), (post, want[1], (0,)), (res, want[2], (0, 1))):
        np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, axes, tuple(-len(axes) + a for a in axes))),
                                   np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(mix.apply(variables, streams(x), pre, method="read")),
                               np.asarray(reference.mix_read(x, want[0])), atol=1e-5)
    wrote = mix.apply(variables, streams(x), y, post, res, method="write")
    np.testing.assert_allclose(np.asarray(jnp.stack(wrote, axis=2)),
                               np.asarray(reference.mix_write(x, y, want[1], want[2])), atol=1e-5)


def test_the_clip_comes_before_the_exp():
    """``beta`` of 100 on one entry: clipped to 30 its ``exp`` is finite and
    the Sinkhorn gives numbers; unclipped it would be ``inf`` and every entry
    of its row and column NaN."""
    x = jax.random.normal(jax.random.key(7), (1, 3, 4, 64))
    mix, variables = _mix_of(x)
    beta = variables["params"]["beta"].at[8].set(100.0)
    pre, post, res = mix.apply({"params": dict(variables["params"], beta=beta)}, streams(x),
                               method="coeff")
    assert np.isfinite(np.asarray(res)).all() and float(res[0, 0].min()) > 0.9


def test_the_seeded_mix_is_not_the_uniform_one(params):
    """The draw's ends (the configuration file's ``weights``): ``H_res``
    diagonal-heavy, NOT symmetric, moving from token to token; ``H_pre`` far
    from uniform; the four streams at the exit as far from each other as from
    zero (a third as far, six sub-blocks after they entered as copies of one
    embedding). A uniform mix would hide a transposed ``H_res`` or a dropped
    Sinkhorn from every probe."""
    world()
    cfg = Xing4Config(**TINY)
    x = reference.enter_streams(jnp.asarray(params["model"]["embed"]["embedding"])[IDS], 4)
    block = params["model"]["layers"]["block"]
    mix = jax.tree.map(lambda a: a[0], block["attn_mix"])
    x = x + 0.5 * jax.random.normal(jax.random.key(8), x.shape)      # streams that already differ
    pre, post, res = (np.asarray(a) for a in reference.mix_coeff(x, mix, 20, 1e-6, -30.0, 30.0))
    diagonal = np.einsum("...ii->...", res) / 4
    assert diagonal.mean() > 0.5                                     # diagonal-heavy
    assert np.abs(res - np.swapaxes(res, -1, -2)).max(axis=(-1, -2)).mean() > 0.02    # not symmetric
    assert res.reshape(-1, 16).std(axis=0).mean() > 0.01             # moves token to token
    assert (pre.max(-1) - pre.min(-1)).mean() > 0.3                  # far from uniform
    # the streams at the exit of the real stack
    at = {}
    model = Xing4ForCausalLM(cfg)
    import neuronx_distributed_tpu.models.llama as llama

    kept = llama.mhc_reduce

    def spy(s):
        at["exit"] = s
        return kept(s)

    llama.mhc_reduce = spy
    try:
        model.apply({"params": params}, jnp.asarray(IDS))
    finally:
        llama.mhc_reduce = kept
    out = np.stack([np.asarray(x_i) for x_i in at["exit"]], axis=2)  # (b, s, 4, C)
    norm = np.linalg.norm(out, axis=-1).mean()
    apart = np.mean([np.linalg.norm(out[:, :, i] - out[:, :, j], axis=-1).mean()
                     for i in range(4) for j in range(i)])
    assert apart > 0.2 * norm      # measured 0.30: six sub-blocks after they entered as copies


# ------------------------------------------------------------- the serving path

@pytest.mark.parametrize("cache", ["paged", "slab"])
def test_insert_and_decode_through_the_latent_cache_equal_the_reference(params, want, cache):
    """Prefill, then every decoded position in the absorbed form with the
    streams one token wide, against the reference's full forward; paged, a
    fourth prompt then shares a page of row 0's prefix and reads the same."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params, cache)
    session = lm.start_session()
    assert distance(cached_logits(lm, session=session), at_cached(want)) <= TOL
    if cache == "slab":
        return
    shared = np.concatenate([IDS[0, :16], np.random.RandomState(9).randint(1, 512, (7,))]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm.insert(session, np.asarray([3]), shared[None], lengths=np.asarray([23]),
                                   reserve_tokens=2))
        alone = np.asarray(reference.forward(params, jnp.asarray(shared[None]), SIZES))[:, -1]
    assert session.paged.stats["prefix_hits"] == 1 and session.insert_ran == (23 - 16, 32)
    assert distance(got, alone) <= TOL


def test_serve_engine_hits_a_prefix_parks_resumes_and_counts_the_mixes(params, tmp_path):
    """Five greedy requests, two sharing a 16-token prefix with an earlier one,
    one parked mid-decode and resumed: each gets the tokens ``generate`` gives it
    alone, and the counters are the host arithmetic they are said to be."""
    world()
    rng = np.random.RandomState(5)
    shared = rng.randint(1, 512, (16,)).astype(np.int32)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32) for n in (9, 20, 13)]
    prompts += [np.concatenate([shared, rng.randint(1, 512, (n,)).astype(np.int32)])
                for n in (5, 9)]
    budget = 2 * 4 + 3
    with jax.default_matmul_precision("highest"):
        alone = tiny.compiled_lm(serving_lm, params, "slab")   # generate() is the slab path's
        solo = [alone.generate(p[None], budget).tokens[0] for p in prompts]
        lm = tiny.compiled_lm(serving_lm, params)
        engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0),
                             park_dir=str(tmp_path / "park"))
        ids = [engine.submit(p, max_new_tokens=budget, arrival_block=0) for p in prompts[:4]]
        engine.step_block()
        assert engine.park(ids[1]) == "parked"
        engine.step_block()
        assert engine.submit(resume=ids[1]) == ids[1]
        while engine.step_block():
            pass
        ids.append(engine.submit(prompts[4], max_new_tokens=budget, arrival_block=engine.blocks))
        while engine.step_block():
            pass
    assert not engine.rejected
    done = {c.request_id: np.asarray(c.tokens) for c in engine.completed}
    for rid, tokens in zip(ids, solo):
        np.testing.assert_array_equal(done[rid], tokens)
    assert engine.session.paged.stats["prefix_hits"] > 0
    assert engine.stats["parked"] == engine.stats["resumed"] == 1 and engine.stats["park_replays"] == 0
    stats = engine.stats
    assert lm.walk_sum_names == ("mhc_mix_steps",)
    # a live row of a step passes all six mixes, and chooses 2 experts in each
    # of the 2 expert layers: both counts are of the same ``live``. A row is
    # live to the end of the block its budget ends in, so no fewer than the
    # decoded tokens
    decoded = sum(len(t) - 1 for t in done.values())
    assert stats["mhc_mix_steps"] * 2 * 2 == stats["moe_assignments"] * SUB_BLOCKS
    assert stats["mhc_mix_steps"] >= SUB_BLOCKS * decoded
    # the inserts' real tokens: the prompts, less what the two prefix hits shared
    assert stats["mhc_mix_tokens"] % SUB_BLOCKS == 0 and stats["mhc_mix_slots"] % (SUB_BLOCKS * 32) == 0
    mixed = stats["mhc_mix_tokens"] // SUB_BLOCKS
    assert sum(p.size for p in prompts) - 2 * 16 <= mixed <= sum(p.size for p in prompts)
    assert stats["mhc_mix_slots"] >= stats["mhc_mix_tokens"]


# -------------------------------------------------------------- the refusals

def _under_tp(params):
    world(tp=2)
    try:
        Xing4ForCausalLM(Xing4Config(**TINY)).apply({"params": params}, jnp.asarray(IDS))
    finally:
        world()


REFUSED = {
    "a_pipeline": lambda p: PipelinedLlama(Xing4Config(**dict(TINY, num_layers=4)), 2, 2),
    "lora_on_the_residual": lambda p: Xing4Config(**dict(TINY, lora_rank=4, lora_slots=2)),
    "sequence_parallel": lambda p: Xing4Config(**dict(TINY, sequence_parallel=True)),
    "context_parallel": lambda p: Xing4Config(**dict(TINY, context_parallel=True)),
    "tensor_parallel": _under_tp,
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_cannot_carry_four_streams_is_refused_by_name(params, what):
    world()
    with pytest.raises(ValueError, match="hc_mult"):
        REFUSED[what](params)


def test_one_stream_is_refused():
    with pytest.raises(ValueError, match="hc_mult"):
        Xing4Config(**dict(TINY, hc_mult=1))


# ---------------------------------- models without ``hc_mult`` are what they were

def _mistral():
    return LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=2,
                       num_heads=4, num_kv_heads=2, max_seq_len=64, dtype=jnp.float32,
                       param_dtype=jnp.float32, use_flash_attention=False, remat_policy=None), \
        LlamaForCausalLM


def _deepseek_v2():
    return DeepseekV2Config(**dict(LATENT, first_k_dense=1, n_shared_experts=2, num_experts=8,
                                   top_k=2, n_group=4, topk_group=2, rope_scaling=YARN)), \
        DeepseekV2ForCausalLM


def _longcat():
    return LongcatFlashConfig(**dict(LATENT, num_layers=2, router_experts=8, num_experts=4,
                                     zero_experts=4, top_k=3)), LongcatFlashForCausalLM


@pytest.mark.parametrize("family", [_mistral, _deepseek_v2, _longcat],
                         ids=["mistral", "deepseek_v2", "longcat_flash"])
def test_without_hc_mult_the_decode_block_holds_nothing_of_the_mix(family, monkeypatch):
    """The seam in ``LlamaModel`` is one ``getattr``: with no ``hc_mult`` the
    parameter tree has no mix, the fused decode block's lowered text names no
    ``mhc_`` scope and the layer scans carry the three-axis hidden state."""
    world()
    import neuronx_distributed_tpu.models.llama as llama

    cfg, model = family()
    carried = []
    step = llama._LayerStep.__call__

    def watching(self, carry, *args, **kw):
        carried.append(carry[0].shape)
        return step(self, carry, *args, **kw)

    monkeypatch.setattr(llama._LayerStep, "__call__", watching)
    params = tiny.make_params(model, cfg, IDS, seed=0)
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not any("mix" in n or "phi" in n for n in names)
    lm = tiny.serving_lm(model, params, cfg)
    lowered = []
    monkeypatch.setattr(jax.stages.Lowered, "compile",
                        lambda self, *a, **k: lowered.append(self.as_text(debug_info=True)) or 1 / 0)
    with pytest.raises(ZeroDivisionError):
        lm.compile_session_decode_fused(4)
    assert lowered and "mhc_" not in lowered[0]
    assert carried and all(len(shape) == 3 for shape in carried)


def test_with_hc_mult_the_programs_name_the_mixs_scopes(params, monkeypatch):
    """``mhc_expand`` hands the embedding on as every stream and traces no op: its
    scope names nothing; the other five name the ops a device trace is sorted by."""
    world()
    lm = serving_lm(params)
    lowered = []
    monkeypatch.setattr(jax.stages.Lowered, "compile",
                        lambda self, *a, **k: lowered.append(self.as_text(debug_info=True)) or 1 / 0)
    with pytest.raises(ZeroDivisionError):
        lm.compile_session_decode_fused(4)
    for scope in ("mhc_coeff", "mhc_sinkhorn", "mhc_read", "mhc_write", "mhc_reduce"):
        assert scope in lowered[0], scope
    assert "stablehlo.while" in lowered[0]          # the Sinkhorn's turns are kept as a loop
