"""``reference/longcat_flash.py`` against the program's own float32 forward,
tiny widths (``test_bm_reference.py``'s comparison, for the configuration PR 52
added), built from the configuration file as the serving driver builds it."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark import run as harness
from benchmark.drivers import serving
from benchmark.reference import longcat_flash

IDS = np.random.RandomState(0).randint(1, 512, (2, 40)).astype(np.int32)
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "longcat-flash-chat")


@pytest.fixture(scope="module")
def tiny():
    """(model, params, sizes) built the way the serving driver builds them,
    from the configuration file's rehearsal widths. A plain apply would drop
    tokens by capacity: all_experts, over the share the file holds."""
    from neuronx_distributed_tpu.parallel import mesh

    mesh.destroy_model_parallel()
    mesh.initialize_model_parallel(tensor_model_parallel_size=1, devices=jax.devices()[:1])
    sizes = harness.load_config(ENTRY, rehearse=True)
    mcfg = serving.model_config(sizes, True, max_seq_len=64, remat_policy=None,
                                moe_mode="all_experts")
    model = serving.load(sizes["builder"]["model"])(mcfg)
    params = meta.unbox(model.init(jax.random.key(1), jnp.asarray(IDS[:, :8])))["params"]

    def shake(path, a):                      # scales of one and a bias of zero would hide a slip
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return a * (1.0 + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape))
        if "e_score_correction_bias" in name:
            return 0.05 * jax.random.normal(jax.random.key(3), a.shape)
        return a

    return model, jax.tree_util.tree_map_with_path(shake, params), sizes


def test_the_builder_maps_the_published_keys(tiny):
    model, params, sizes = tiny
    cfg = model.config
    assert (cfg.num_experts, cfg.router_experts, cfg.zero_experts, cfg.top_k) == (4, 16, 8, 6)
    assert (cfg.num_layers, cfg.kv_layers, cfg.num_heads, cfg.num_kv_heads) == (2, 4, 4, 4)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (96, 32)
    assert (cfg.q_lora_scale, cfg.kv_lora_scale) == ((64 / 48) ** 0.5, 2 ** 0.5)
    assert cfg.routed_scaling_factor == sizes["routed_scaling_factor"] == 6
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.norm_topk_prob) == (1e7, 1e-5, False)
    block = params["model"]["layers"]["block"]
    assert block["sub_1"]["attention"]["q_b_proj"].shape == (2, 48, 4, 24)
    assert block["sub_1"]["mlp"]["gate_proj"]["kernel"].shape == (2, 64, 96)
    assert block["moe"]["router"]["kernel"].shape == (2, 64, 24)
    assert block["moe"]["experts"]["down"].shape == (2, 4, 32, 64)
    assert params["lm_head"]["kernel"].shape == (64, 512)


def test_reference_forward_equals_the_programs_float32_forward(tiny):
    model, params, sizes = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
    want = np.asarray(longcat_flash.forward(params, jnp.asarray(IDS), sizes))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_reference_positions_pick_rows_of_the_full_logits(tiny):
    _, params, sizes = tiny
    full = np.asarray(longcat_flash.forward(params, jnp.asarray(IDS), sizes))
    pick = np.asarray([[3, 39], [0, 17]])
    some = np.asarray(longcat_flash.forward(params, jnp.asarray(IDS), sizes, positions=pick))
    assert np.allclose(some, full[np.arange(2)[:, None], pick], atol=1e-5)


@pytest.mark.parametrize("other", [
    {"routed_scaling_factor": 1.0}, {"mla_scale_q_lora": False}, {"mla_scale_kv_lora": False},
    {"moe_topk": 5}, {"experts_held_first": 8}, {"rope_theta": 10000.0}, {"rms_norm_eps": 1e-2}],
    ids=lambda d: next(iter(d)))
def test_another_value_of_the_file_is_another_model(tiny, other):
    _, params, sizes = tiny
    want = np.asarray(longcat_flash.forward(params, jnp.asarray(IDS), sizes))
    got = np.asarray(longcat_flash.forward(params, jnp.asarray(IDS), {**sizes, **other}))
    assert np.abs(got - want).max() > 1e-3 * np.abs(want).max()


def test_the_identity_experts_are_the_routers_last_columns(tiny):
    """A router narrower than ``router_experts + zero_expert_num`` is refused,
    and the identity part is the chosen weights of the last columns times the
    layer's input: with every real expert's weights zero the expert layer gives
    exactly that."""
    _, params, sizes = tiny
    with pytest.raises(AssertionError):
        longcat_flash.forward(params, jnp.asarray(IDS), {**sizes, "zero_expert_num": 4})
    moe = jax.tree.map(lambda a: a, params["model"]["layers"]["block"]["moe"])
    moe["experts"] = jax.tree.map(jnp.zeros_like, moe["experts"])
    u = jnp.asarray(np.random.RandomState(1).normal(size=(2, 5, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(longcat_flash.experts(u, moe, 1, sizes))
        weights = longcat_flash.route(u, moe["router"]["kernel"][1],
                                      moe["router"]["e_score_correction_bias"][1], 6, 6.0)
    np.testing.assert_allclose(
        got, np.asarray(jnp.sum(weights[..., 16:], -1, keepdims=True) * u), rtol=1e-6)
    assert (np.asarray(weights > 0).sum(-1) == 6).all()
