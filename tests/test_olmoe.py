"""OLMoE (``models/olmoe.py``) against its plain reference, tiny widths, float32.

64 experts top-8 become 16 top-4; QK-norm over all heads and the router's
un-renormalised top-k are as published. Weights are seeded random; the norm
scales (``q_norm``/``k_norm`` included) are shaken away from one so that a
scale applied to the wrong axis shows.

The tolerance: float32 against float32 under ``highest`` matmul precision, so
only the order of additions differs (the program sums all experts in one
einsum, the reference adds them one at a time; attention is grouped by KV head
in the cache path): logits within 1e-4 of the reference's largest. That is
tight enough to see the mathematics: renormalising the four kept router
weights moves the logits by 34 times that and leaving QK-norm out by 2 700
times (``test_wrong_mathematics_fails``); the right mathematics sits at 3e-7.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark.reference import olmoe as reference
from neuronx_distributed_tpu.inference import ServeEngine
from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM, dbrx
from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, OlmoeForCausalLM, olmoe_1b_7b
from neuronx_distributed_tpu.parallel import mesh
from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings
from tests import tiny
from tests.tiny import IDS, LENS, STEPS, at_cached, cached_logits, distance, world

TOL = 1e-4
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2, num_heads=4,
            num_kv_heads=4, num_experts=16, top_k=4, max_seq_len=64, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None, moe_mode="all_experts")
SIZES = {"rms_norm_eps": 1e-5, "rope_theta": 10000.0, "num_experts_per_tok": 4,
         "norm_topk_prob": False}
full_forward = functools.partial(tiny.full_forward, OlmoeForCausalLM)
# served with every expert computed, as the forward is: what is compared is the cache
serving_lm = functools.partial(tiny.serving_lm, OlmoeForCausalLM, cfg=OlmoeConfig(**TINY),
                               moe_mode="all_experts")


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(OlmoeForCausalLM, OlmoeConfig(**TINY), IDS, tiny.shake_norms)


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(reference.forward(params, jnp.asarray(IDS), SIZES))


def test_preset_is_the_published_configuration():
    cfg = olmoe_1b_7b()
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers) == (2048, 1024, 16)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (16, 16, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.norm_topk_prob, cfg.qk_norm) == (64, 8, False, True)
    assert (cfg.vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == (50304, 10000.0, 1e-5)
    assert not cfg.tie_word_embeddings and cfg.qkv_clip is None


def test_full_forward_equals_the_reference(params, want):                       # (a)
    world()
    assert distance(full_forward(OlmoeConfig(**TINY), params), want) <= TOL


@pytest.mark.parametrize("wrong", [dict(norm_topk_prob=True), dict(qk_norm=False)],
                         ids=["renormalised", "no_qk_norm"])
def test_wrong_mathematics_fails(params, want, wrong):                          # (c)
    world()
    got = full_forward(OlmoeConfig(**{**TINY, **wrong}), params)
    assert distance(got, want) > 10 * TOL
    if "norm_topk_prob" in wrong:       # and the reference has the switch the other way
        renorm = reference.forward(params, jnp.asarray(IDS), {**SIZES, "norm_topk_prob": True})
        assert distance(got, np.asarray(renorm)) <= TOL


def test_prefill_and_decode_through_the_paged_cache_equal_the_reference(params, want):   # (b)
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params)
    assert distance(cached_logits(lm), at_cached(want)) <= TOL


def test_serve_engine_fused_blocks_follow_the_reference(params):                 # (b)
    """The engine gives tokens, so the comparison is made in the reference's
    logits: at every generated position the token the engine chose has, in
    the reference's full forward over [prompt, generated], the largest logit
    to within the tolerance."""
    world()
    with jax.default_matmul_precision("highest"):
        lm = tiny.compiled_lm(serving_lm, params)
        engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0))
        prompts = [IDS[i, :n] for i, n in enumerate(LENS)]
        ids = [engine.submit(p, max_new_tokens=STEPS + 1, arrival_block=0) for p in prompts]
        while engine.step_block():
            pass
    done = {c.request_id: np.asarray(c.tokens) for c in engine.completed}
    assert not engine.rejected and all(len(done[i]) == STEPS + 1 for i in ids)
    for rid, prompt in zip(ids, prompts):
        seq = np.concatenate([prompt, done[rid]])
        logits = np.asarray(reference.forward(params, jnp.asarray(seq[None]), SIZES))[0]
        at = logits[len(prompt) - 1: len(seq) - 1]                     # (STEPS + 1, vocab)
        chosen = at[np.arange(len(at)), done[rid]]
        assert ((at.max(-1) - chosen) <= TOL * np.abs(logits).max()).all()


@pytest.mark.parametrize("tp", [2, 4])
def test_tensor_parallel_logits_equal_tp1(params, want, tp):                     # (d)
    """QK-norm's mean of squares crosses the head shards (4 heads over 2 and
    4 devices); the expert width and the vocabulary are sharded too."""
    world(tp)
    cfg = OlmoeConfig(**TINY)
    model = OlmoeForCausalLM(cfg)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.asarray(IDS)))
    specs = nn.get_partition_spec(abstract)["params"]
    assert "tp" in tuple(specs["model"]["layers"]["block"]["attention"]["q_norm"])
    sharded = jax.device_put(params, specs_to_shardings(specs, mesh.get_mesh()))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: model.apply({"params": p}, i))(
            sharded, jnp.asarray(IDS)))
        assert distance(got, want) <= TOL
        lm = serving_lm(sharded).compile()
    assert distance(cached_logits(lm), at_cached(want)) <= TOL


OFF = {
    "mixtral": MixtralConfig(**{**TINY, "num_kv_heads": 2}),
    "dbrx": dbrx(**{**TINY, "num_kv_heads": 2}),
    "olmoe_switched_off": OlmoeConfig(**TINY, qk_norm=False, norm_topk_prob=True),
}


@pytest.mark.parametrize("name", sorted(OFF))
def test_switched_off_there_is_no_parameter_and_no_op(name):                     # (e)
    world()
    cfg = OFF[name]
    assert not cfg.qk_norm and cfg.norm_topk_prob
    off = tiny.make_params(MixtralForCausalLM, cfg, IDS, tiny.shake_norms)
    leaves = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(off)]
    assert not [n for n in leaves if "q_norm" in n or "k_norm" in n]
    lm = tiny.serving_lm(MixtralForCausalLM, off, cfg, moe_mode="all_experts")
    # (switched on, tests/test_scope_names.py reads the scope back from OLMoE's step)
    assert "qk_norm" not in lm.compile_session_decode_fused(4).as_text()


# --- the routing counter of the fused decode block ---------------------------

def run_engine(lm, **kw):
    """Three requests admitted together, each 1 + 8 tokens: the first comes
    from the insert, the other eight from two whole fused blocks of four."""
    engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0), **kw)
    for i, n in enumerate(LENS):
        engine.submit(IDS[i, :n], max_new_tokens=9, arrival_block=0)
    while engine.step_block():
        pass
    assert len(engine.completed) == 3 and not engine.rejected
    return {k: int(engine.stats[k]) for k in
            ("moe_experts_touched", "moe_assignments", "moe_layer_steps")}


def test_fused_decode_counts_what_the_router_chose(params):
    world()
    cfg = OlmoeConfig(**TINY)
    lm = serving_lm(params)
    assert lm.moe_stats
    assert len(jax.tree.leaves(lm.compile_session_decode_fused(4).out_info)) == len(
        jax.tree.leaves(lm._cache_avals())) + 6     # rows, how far it read, what it routed
    # ... how far, in how many steps, of how many rows; then the three routing sums
    assert [leaf.shape for leaf in lm.compile_session_decode_fused(4).out_info[-2:]] == [(3,), (3,)]
    got = run_engine(lm)
    steps, rows = 8, 3
    assert got["moe_layer_steps"] == cfg.num_layers * steps
    assert got["moe_assignments"] == rows * cfg.top_k * cfg.num_layers * steps
    # every layer step touches at least top_k experts and at most what three rows can choose
    assert (cfg.top_k * got["moe_layer_steps"] <= got["moe_experts_touched"]
            <= min(cfg.num_experts, rows * cfg.top_k) * got["moe_layer_steps"])
    assert got["moe_experts_touched"] < got["moe_assignments"]     # some experts are shared
    assert run_engine(lm, async_loop=True) == got                   # the pipelined harvest too


def test_a_dense_model_counts_nothing_and_returns_what_it_did():
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    world()
    dense = {k: v for k, v in TINY.items() if k not in ("num_experts", "top_k", "moe_mode")}
    cfg = LlamaConfig(**dense)
    lm = tiny.serving_lm(LlamaForCausalLM, tiny.make_params(LlamaForCausalLM, cfg, IDS), cfg)
    assert not lm.moe_stats
    assert len(jax.tree.leaves(lm.compile_session_decode_fused(4).out_info)) == len(
        jax.tree.leaves(lm._cache_avals())) + 5     # rows, how far it read
    assert lm.compile_session_decode_fused(4).out_info[-1].shape == (3,)   # ... and of how many rows
    assert "moe_stats" not in lm.compile_session_decode_fused(4).as_text()
    assert run_engine(lm) == {"moe_experts_touched": 0, "moe_assignments": 0,
                              "moe_layer_steps": 0}
