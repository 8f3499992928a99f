"""``reference/deepseek_v32.py`` against the program's own float32 forward,
tiny widths (``test_bm_reference.py``'s comparison, for the configuration PR 56
added), built from the configuration file as the serving driver builds it; and
each piece the reference writes out on its own against hand arithmetic."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark import run as harness
from benchmark.drivers import serving
from benchmark.reference import deepseek_v32

IDS = np.random.RandomState(0).randint(1, 512, (2, 48)).astype(np.int32)
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "deepseek-v3.2")


@pytest.fixture(scope="module")
def tiny():
    """(model, params, sizes) built the way the serving driver builds them,
    from the configuration file's rehearsal widths (``index_topk`` 32 under 48
    tokens: the last third of every row chooses). A plain apply would drop
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
        noise = jax.random.normal(jax.random.key(a.size), a.shape)
        if "e_score_correction_bias" in name or name.endswith("['index_k_norm']['bias']"):
            return 0.1 * noise
        if "norm" in name:
            return a * (1.0 + 0.3 * noise)
        return a

    return model, jax.tree_util.tree_map_with_path(shake, params), sizes


def test_the_builder_maps_the_published_keys(tiny):
    model, params, sizes = tiny
    cfg = model.config
    assert (cfg.num_experts, cfg.router_experts, cfg.top_k, cfg.n_group, cfg.topk_group) == \
        (4, 16, 4, 4, 2)
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (32, 4, 16)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_heads, cfg.n_shared_experts) == (3, 1, 4, 1)
    assert (cfg.scoring_func, cfg.group_score, cfg.router_selection_bias, cfg.norm_topk_prob) == \
        ("sigmoid", "top2_sum", True, True)
    assert cfg.routed_scaling_factor == sizes["routed_scaling_factor"] == 2.5
    attention = params["model"]["layers"]["block"]["attention"]
    assert attention["index_q_proj"].shape == (2, 48, 4, 16)
    assert attention["index_k_proj"].shape == (2, 64, 16)
    assert attention["index_weights_proj"].shape == (2, 64, 4)
    assert set(attention["index_k_norm"]) == {"scale", "bias"}
    moe = params["model"]["layers"]["block"]["moe"]
    assert moe["router"]["e_score_correction_bias"].shape == (2, 16)
    assert moe["experts"]["down"].shape == (2, 4, 32, 64)
    assert "mlp" in params["model"]["dense_layers"]["block"]


def test_reference_forward_equals_the_programs_float32_forward(tiny):
    model, params, sizes = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
    want = np.asarray(deepseek_v32.forward(params, jnp.asarray(IDS), sizes))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_reference_positions_pick_rows_of_the_full_logits(tiny):
    _, params, sizes = tiny
    full = np.asarray(deepseek_v32.forward(params, jnp.asarray(IDS), sizes))
    pick = np.asarray([[3, 47], [0, 40]])
    some = np.asarray(deepseek_v32.forward(params, jnp.asarray(IDS), sizes, positions=pick))
    assert np.allclose(some, full[np.arange(2)[:, None], pick], atol=1e-5)


@pytest.mark.parametrize("other", [
    {"index_topk": 24}, {"index_topk": 48}, {"routed_scaling_factor": 1.0},
    {"norm_topk_prob": False}, {"topk_group": 4}, {"num_experts_per_tok": 3},
    {"experts_held_first": 8}, {"rope_theta": 100.0}, {"rms_norm_eps": 1e-2}],
    ids=lambda d: "-".join(f"{k}_{v}" for k, v in d.items()))
def test_another_value_of_the_file_is_another_model(tiny, other):
    """``index_topk`` 48 is dense attention over these 48 tokens: the choice
    is at work in the comparison above."""
    _, params, sizes = tiny
    want = np.asarray(deepseek_v32.forward(params, jnp.asarray(IDS), sizes))
    got = np.asarray(deepseek_v32.forward(params, jnp.asarray(IDS), {**sizes, **other}))
    assert np.abs(got - want).max() > 1e-3 * np.abs(want).max()


def test_the_choice_is_the_k_largest_of_the_visible_by_hand():
    scores = jnp.asarray([[0.0, 9.0, 9.0, 9.0],
                          [5.0, 1.0, 9.0, 9.0],
                          [2.0, 7.0, 7.0, 9.0],
                          [2.0, 7.0, 7.0, 1.0]])
    got = np.asarray(deepseek_v32.chosen_mask(scores, 2))
    assert got.tolist() == [[True, False, False, False],      # alone
                            [True, True, False, False],       # two visible: both
                            [False, True, True, False],       # the two largest of three
                            [False, True, True, False]]       # 7, 7 over 2 and 1
    tie = np.asarray(deepseek_v32.chosen_mask(jnp.asarray([[0.0] * 4] * 4), 2))
    assert tie[3].tolist() == [True, True, False, False]       # a tie: the lower positions


def test_the_index_score_is_the_weighted_relu_by_hand():
    q = jnp.asarray([[[1.0, 0.0], [0.0, 1.0]]] * 2)             # (s=2, heads=2, d=2)
    k = jnp.asarray([[2.0, -3.0], [-1.0, 4.0]])                 # (s=2, d=2)
    w = jnp.asarray([[0.5, 2.0], [1.0, -1.0]])                  # (s=2, heads=2)
    got = np.asarray(deepseek_v32.index_scores(q, k, w))
    # query 0: head 0 . k = [2, -1] -> relu [2, 0]; head 1 . k = [-3, 4] -> relu [0, 4]
    np.testing.assert_allclose(got[0], [0.5 * 2, 2.0 * 4])
    np.testing.assert_allclose(got[1], [1.0 * 2, -1.0 * 4])


def test_the_route_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, top-2: the bias moves the
    CHOICE (group 3 over group 0) and not the weights."""
    logits = jnp.asarray([[2.0, 2.0, 0.0, 0.0, -1.0, -1.0, 1.5, 1.5]])
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    none = np.asarray(deepseek_v32.route(logits, np.eye(8, dtype=np.float32),
                                         np.zeros(8, np.float32), 2, 4, 2, True, 2.5))[0]
    assert np.flatnonzero(none).tolist() == [0, 1]
    np.testing.assert_allclose(none[[0, 1]], 2.5 * s[[0, 1]] / s[[0, 1]].sum(), rtol=1e-6)
    bias = np.zeros(8, np.float32)
    bias[[6, 7]] = 0.5
    moved = np.asarray(deepseek_v32.route(logits, np.eye(8, dtype=np.float32), bias,
                                          2, 4, 2, True, 2.5))[0]
    assert np.flatnonzero(moved).tolist() == [6, 7]
    np.testing.assert_allclose(moved[[6, 7]], 2.5 * s[[6, 7]] / s[[6, 7]].sum(), rtol=1e-6)
    kept = np.asarray(deepseek_v32.route(logits, np.eye(8, dtype=np.float32), bias,
                                         2, 4, 2, False, 1.0))[0]
    np.testing.assert_allclose(kept[[6, 7]], s[[6, 7]], rtol=1e-6)
