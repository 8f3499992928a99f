"""``reference/laguna.py`` against the program's own float32 forward, tiny
widths (``test_bm_reference.py``'s comparison, for the configuration PR 49
added), built from the configuration file as the serving driver builds it."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark import run as harness
from benchmark.drivers import serving
from benchmark.reference import laguna

IDS = np.random.RandomState(0).randint(1, 512, (2, 40)).astype(np.int32)
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "laguna-s-2.1")


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

    def shake(path, a):                      # scales of one would hide a misplaced one
        if "norm" in jax.tree_util.keystr(path):
            return a * (1.0 + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape))
        return a

    return model, jax.tree_util.tree_map_with_path(shake, params), sizes


def test_the_builder_maps_the_published_keys(tiny):
    model, params, sizes = tiny
    cfg = model.config
    assert (cfg.num_experts, cfg.router_experts, cfg.top_k) == (8, 16, 3)
    assert (cfg.sliding_window, cfg.ring, cfg.period, cfg.num_layers) == (8, 8, 4, 5)
    assert cfg.scoring_func == sizes["scoring_func"] and cfg.attention_gate == sizes["gating"]
    periods = params["model"]["periods"]
    assert sorted(periods) == ["full_attention_3", "sliding_attention_0", "sliding_attention_1",
                               "sliding_attention_2"]
    assert periods["sliding_attention_0"]["attention"]["qkv"]["q_kernel"].shape == (1, 64, 6, 16)
    assert periods["full_attention_3"]["attention"]["gate_kernel"].shape == (1, 64, 4)
    assert periods["full_attention_3"]["moe"]["router"]["kernel"].shape == (1, 64, 16)
    assert params["model"]["first"]["block"]["mlp"]["gate_proj"]["kernel"].shape == (1, 64, 96)


def test_reference_forward_equals_the_programs_float32_forward(tiny):
    """40 tokens through a window of 8, past YaRN's original 64? no: inside
    it; the serving tests go past. float32 against float32."""
    model, params, sizes = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
    want = np.asarray(laguna.forward(params, jnp.asarray(IDS), sizes))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_reference_positions_pick_rows_of_the_full_logits(tiny):
    _, params, sizes = tiny
    full = np.asarray(laguna.forward(params, jnp.asarray(IDS), sizes))
    pick = np.asarray([[3, 39], [0, 17]])
    some = np.asarray(laguna.forward(params, jnp.asarray(IDS), sizes, positions=pick))
    assert np.allclose(some, full[np.arange(2)[:, None], pick], atol=1e-5)


@pytest.mark.parametrize("other", [
    {"scoring_func": "softmax"}, {"sliding_window": 9}, {"gating": "none"},
    {"moe_routed_scaling_factor": 1.0}, {"experts_held_first": 8}, {"norm_topk_prob": False}],
    ids=lambda d: next(iter(d)))
def test_another_value_of_the_file_is_another_model(tiny, other):
    _, params, sizes = tiny
    want = np.asarray(laguna.forward(params, jnp.asarray(IDS), sizes))
    got = np.asarray(laguna.forward(params, jnp.asarray(IDS), {**sizes, **other}))
    assert np.abs(got - want).max() > 1e-3 * np.abs(want).max()


def test_the_reference_refuses_a_gate_it_does_not_know(tiny):
    _, params, sizes = tiny
    with pytest.raises(ValueError, match="gating"):
        laguna.forward(params, jnp.asarray(IDS), {**sizes, "gating": "per-element"})
