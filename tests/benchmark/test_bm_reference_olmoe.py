"""``reference/olmoe.py`` against the program's own float32 forward, tiny
widths (``test_bm_reference.py``'s comparison, for the configuration PR 26
added), and against the configuration file that names it."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark import opcount
from benchmark import run as harness
from benchmark.drivers import serving
from benchmark.reference import olmoe

IDS = np.random.RandomState(0).randint(1, 512, (2, 24)).astype(np.int32)
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "olmoe-1b-7b")


@pytest.fixture(scope="module")
def tiny():
    """(model, params, sizes) built the way the serving driver builds them,
    from the configuration file's rehearsal widths."""
    from neuronx_distributed_tpu.parallel import mesh

    mesh.destroy_model_parallel()
    mesh.initialize_model_parallel(tensor_model_parallel_size=1, devices=jax.devices()[:1])
    sizes = harness.load_config(ENTRY, rehearse=True)
    # serving never drops a token (MoE: decode -> all-experts); a plain apply
    # would run the training default, capacity-factor dispatch
    mcfg = serving.model_config(sizes, True, max_seq_len=64, remat_policy=None,
                                moe_mode="all_experts")
    model = serving.load(sizes["builder"]["model"])(mcfg)
    params = meta.unbox(model.init(jax.random.key(1), jnp.asarray(IDS)))["params"]

    def shake(path, a):                      # scales of one would hide a misplaced one
        if "norm" in jax.tree_util.keystr(path):
            return a * (1.0 + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape))
        return a

    return model, jax.tree_util.tree_map_with_path(shake, params), sizes


def test_the_builder_maps_the_published_switches(tiny):
    model, params, sizes = tiny
    cfg = model.config
    assert cfg.qk_norm and not cfg.norm_topk_prob
    assert (cfg.num_experts, cfg.top_k) == (sizes["num_experts"], sizes["num_experts_per_tok"])
    attention = params["model"]["layers"]["block"]["attention"]
    assert attention["q_norm"].shape == (cfg.num_layers, cfg.num_heads * cfg.head_dim_)
    assert attention["k_norm"].shape == (cfg.num_layers, cfg.num_kv_heads * cfg.head_dim_)


def test_reference_forward_equals_the_programs_float32_forward(tiny):
    model, params, sizes = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
    want = np.asarray(olmoe.forward(params, jnp.asarray(IDS), sizes))
    # float32 against float32: only the order of additions differs
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_reference_positions_pick_rows_of_the_full_logits(tiny):
    _, params, sizes = tiny
    full = np.asarray(olmoe.forward(params, jnp.asarray(IDS), sizes))
    pick = np.asarray([[3, 23], [0, 7]])
    some = np.asarray(olmoe.forward(params, jnp.asarray(IDS), sizes, positions=pick))
    assert np.allclose(some, full[np.arange(2)[:, None], pick], atol=1e-5)


def test_renormalised_weights_are_a_different_model(tiny):
    _, params, sizes = tiny
    want = np.asarray(olmoe.forward(params, jnp.asarray(IDS), sizes))
    other = np.asarray(olmoe.forward(params, jnp.asarray(IDS), {**sizes, "norm_topk_prob": True}))
    assert np.abs(other - want).max() > 1e-3 * np.abs(want).max()


def test_opcount_reads_the_experts_of_the_published_configuration():
    """``opcount.experts`` reads Mixtral's key; the file carries it beside the
    published ``num_experts``, or the roofline share would count a dense model."""
    cfg = harness.load_config(ENTRY, rehearse=False)
    assert opcount.experts(cfg) == (cfg["num_experts"], cfg["num_experts_per_tok"]) == (64, 8)
    assert opcount.expert_params(cfg) == 3 * 2048 * 1024
    layer = opcount.layer_params(cfg)
    assert layer == 64 * 3 * 2048 * 1024 + 4 * 2048 * 2048 + 2048 * 64       # 419.6 M
    # three live rows choose at most 24 experts; eight rows all 64
    few = opcount.decode_step_bytes(cfg, 3, 0) - opcount.decode_step_bytes(cfg, 8, 0)
    assert few == -cfg["num_hidden_layers"] * 40 * opcount.expert_params(cfg) * 2
