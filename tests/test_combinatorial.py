"""Combinatorial parallelism parity sweep (reference
``test/integration/combinatorial_tests`` — config files named
``test_TP{8,32}_SP{0,1}_SC0_PP{1,4}_Zero1Opt{0,1}_FP32.txt`` driven through a
shared run.sh and compared against stored loss baselines; SURVEY §4.2).

Here the baseline is computed, not stored: the SAME tiny Llama with the SAME
init and data must produce the SAME 3-step loss trajectory under every
parallelism combination — TP, TP+SP, CP, EP-meshed, ZeRO on/off, PP, and
mixtures. Catches cross-feature interference that per-feature goldens miss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=32, dtype=jnp.float32,
    use_flash_attention=False, remat_policy=None,
)
STEPS = 3


def _run(mesh_kw, model_over, zero1=True, steps=STEPS, step_kwargs=None):
    """Loss trajectory for one parallelism combination (fixed init/data)."""
    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    ps.initialize_model_parallel(**mesh_kw)
    cfg = neuronx_distributed_config(
        optimizer_config={"zero_one_enabled": zero1},
    )
    lcfg = LlamaConfig(**{**TINY, **model_over})
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 127, (4, 32))
    labels = rs.randint(0, 127, (4, 32))
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-3,
                                        weight_decay=0.0)
    if step_kwargs and step_kwargs.get("optimizer_kernel"):
        # guard against silent fallback to the declarative path (which would
        # make a kernel-parity combo compare the default against itself)
        assert hasattr(opt.tx, "update_and_params_local")
    state = create_train_state(model, opt)

    def loss_fn(params, b, rng):
        return model.module.apply({"params": params}, b["ids"], b["labels"],
                                  method=LlamaForCausalLM.loss)

    step = make_train_step(model, opt, loss_fn, **(step_kwargs or {}))
    losses = []
    for i in range(steps):
        state, m = step(state, {"ids": ids, "labels": labels}, jax.random.key(i))
        losses.append(float(m["loss"]))
    ps.destroy_model_parallel()
    return losses


COMBOS = {
    "TP2": (dict(tensor_model_parallel_size=2), {}, True),
    "TP4": (dict(tensor_model_parallel_size=4), {}, True),
    "TP2_SP1": (dict(tensor_model_parallel_size=2),
                {"sequence_parallel": True}, True),
    "TP2_Zero1Off": (dict(tensor_model_parallel_size=2), {}, False),
    "CP2": (dict(context_parallel_size=2), {"context_parallel": True}, True),
    "TP2_CP2": (dict(tensor_model_parallel_size=2, context_parallel_size=2),
                {"context_parallel": True}, True),
    "TP2_EPmesh2": (dict(tensor_model_parallel_size=2,
                         expert_model_parallel_size=2), {}, True),
}


@pytest.fixture(scope="module")
def baseline():
    return _run(dict(tensor_model_parallel_size=1), {}, True)


@pytest.mark.parametrize("name", sorted(COMBOS))
def test_combo_matches_baseline(name, baseline):
    mesh_kw, model_over, zero1 = COMBOS[name]
    losses = _run(mesh_kw, model_over, zero1)
    np.testing.assert_allclose(losses, baseline, rtol=5e-4,
                               err_msg=f"combo {name} diverged from baseline")


def test_pp2_tp2_matches_baseline(baseline):
    """PP uses the pipelined model object; microbatched loss must still track
    the dense trajectory."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 pipeline_model_parallel_size=2)
    cfg = neuronx_distributed_config(optimizer_config={"zero_one_enabled": True})
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 127, (4, 32))
    labels = rs.randint(0, 127, (4, 32))
    pm = PipelinedLlama(LlamaConfig(**TINY), num_stages=2, num_microbatches=2,
                        remat=False)
    model = pm.as_parallel_model(jnp.asarray(ids))
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-3,
                                        weight_decay=0.0)
    state = create_train_state(model, opt)
    step = make_train_step(model, opt,
                           lambda p, b, r: pm.loss(p, b["ids"], b["labels"]))
    losses = []
    for i in range(STEPS):
        state, m = step(state, {"ids": ids, "labels": labels}, jax.random.key(i))
        losses.append(float(m["loss"]))
    ps.destroy_model_parallel()
    # PP inits params via its own key order — trajectories match in SHAPE of
    # descent, not bit-exactly; assert same scale and monotone consistency
    np.testing.assert_allclose(losses[0], baseline[0], rtol=0.05)
    assert losses[-1] < losses[0]


def test_pp2_vpp_1f1b_matches_pp2_gpipe_exactly():
    """Cross-engine interference check: the table-driven interleaved-1F1B
    trajectory must equal the gpipe-interleaved trajectory bit-for-bit-ish —
    same init (VPP layout), same data, only the schedule differs."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama

    cfg = LlamaConfig(**{**TINY, "num_layers": 4})
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 127, (4, 32))
    labels = rs.randint(0, 127, (4, 32))

    def run(schedule):
        if ps.model_parallel_is_initialized():
            ps.destroy_model_parallel()
        ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                     pipeline_model_parallel_size=2)
        ncfg = neuronx_distributed_config(
            optimizer_config={"zero_one_enabled": True})
        pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=2,
                            num_chunks=2, remat=False, schedule=schedule)
        model = pm.as_parallel_model(jnp.asarray(ids))
        opt = initialize_parallel_optimizer(ncfg, model, learning_rate=1e-3,
                                            weight_decay=0.0)
        state = create_train_state(model, opt)
        step = make_train_step(
            model, opt, lambda p, b, r: pm.loss(p, b["ids"], b["labels"]))
        losses = []
        for i in range(STEPS):
            state, m = step(state, {"ids": ids, "labels": labels},
                            jax.random.key(i))
            losses.append(float(m["loss"]))
        ps.destroy_model_parallel()
        return losses

    np.testing.assert_allclose(run("1f1b"), run("gpipe"), rtol=2e-5)


def test_tp2_optimizer_kernel_matches_baseline(baseline):
    """The shard_map + Pallas optimizer path (interpreted on CPU) under
    TP x ZeRO-1 must reproduce the declarative path's trajectory."""
    losses = _run(dict(tensor_model_parallel_size=2), {}, True,
                  step_kwargs={"optimizer_kernel": True})
    np.testing.assert_allclose(losses, baseline, rtol=5e-4)
