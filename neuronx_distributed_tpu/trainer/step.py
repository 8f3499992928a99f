"""The jitted training step: one compiled XLA program per step.

Reference call stack (SURVEY §3.2): ``NxDModel.run_train`` → forward →
``loss.backward()`` → ``NxDOptimizer.step`` → ``xm.mark_step()``, where the
mark_step fuses the whole step into one XLA program. On TPU/JAX the jitted
``train_step`` IS that program — forward, backward, grad clip, optimizer
update, all scheduled together by XLA, with buffer donation replacing the
reference's manual memory management.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

import optax

from neuronx_distributed_tpu.parallel.grads import clip_grad_norm
from neuronx_distributed_tpu.trainer.model import ParallelModel
from neuronx_distributed_tpu.trainer.optimizer import NxDOptimizer

PyTree = Any


class TrainState(struct.PyTreeNode):
    """Step counter + params + optimizer state (the reference keeps these on
    the model/optimizer objects; functional JAX keeps them in one pytree that
    the step consumes and re-emits with donated buffers)."""

    step: jax.Array
    params: PyTree
    opt_state: PyTree


def create_train_state(model: ParallelModel, optimizer: NxDOptimizer) -> TrainState:
    """Initialize optimizer state sharded per the ZeRO-1 plan (state is born
    sharded, like params — no scatter after the fact). With LoRA active,
    ``state.params`` is the ADAPTER tree; the frozen base stays on the model."""
    opt_state = jax.jit(
        optimizer.init, out_shardings=_opt_state_shardings(model, optimizer)
    )(model.trainable_params)
    # the counter is born on the mesh like everything else in the state: the
    # step hands it back replicated over the mesh, and an input whose
    # sharding differs from the first call's makes jit trace and compile the
    # whole step a second time
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(model.mesh, P()))
    return TrainState(step=step, params=model.trainable_params, opt_state=opt_state)


def _opt_state_shardings(model: ParallelModel, optimizer: NxDOptimizer):
    abstract = jax.eval_shape(optimizer.init, model.trainable_params)
    return optimizer.zero1_plan.opt_state_shardings(abstract)


def make_train_step(
    model: ParallelModel,
    optimizer: NxDOptimizer,
    loss_fn: Callable[..., jax.Array],
    donate: bool = True,
    grad_accum_steps: int = 1,
    optimizer_kernel: Optional[bool] = None,
) -> Callable[[TrainState, PyTree, jax.Array], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted step.

    ``loss_fn(params, batch, rng) -> scalar loss`` must call
    ``model.apply`` inside; the batch should be sharded over the DP mesh axes
    (use ``mesh.data_pspec()``) — GSPMD then emits the DP grad all-reduce
    inside this same program (reference ``bucket_allreduce_gradients``
    equivalence, see parallel/grads.py).

    ``grad_accum_steps > 1`` (the reference's ``grad_accum_usteps``,
    run_llama_nxd_ptl.py:171 / module_llama.py:105): the batch's leading dim
    splits into that many microbatches and a ``lax.scan`` accumulates
    fp32-mean gradients INSIDE this one program — one optimizer update, one
    DP all-reduce, no per-microbatch host roundtrips (the reference loops
    eagerly and divides the loss by the accumulation count)."""
    mesh = model.mesh
    param_shardings = model.trainable_shardings()
    opt_shardings = _opt_state_shardings(model, optimizer)
    # Pallas optimizer kernel (optimizer/fused_kernel.py): OPT-IN only.
    # Measured on-chip at the bench shapes (PROFILE.md round 4) the
    # per-block pipeline overhead made it ~2x slower than XLA's fused
    # elementwise chain — the declarative path already sits near the HBM
    # roofline here. Kept as an option (and CI-covered under the Pallas
    # interpreter) because the shard_map + ZeRO-resharding harness is the
    # right structure if a future Mosaic revision changes the tradeoff.
    if optimizer_kernel is None:
        optimizer_kernel = False
    use_kernel = optimizer_kernel and hasattr(optimizer.tx, "update_and_params_local")
    # per-leaf ZeRO resharding plan: (dim, extra DP axes) where the state
    # spec shards a dim beyond the param spec, else None
    _kernel_plan: Dict[str, Any] = {}
    if use_kernel:
        from neuronx_distributed_tpu.optimizer.zero1 import _entry_axes

        pflat = jax.tree_util.tree_flatten_with_path(param_shardings)[0]
        sflat = jax.tree_util.tree_flatten_with_path(opt_shardings.master)[0]
        for (ppath, psh), (_, ssh) in zip(pflat, sflat):
            pe, se = list(psh.spec), list(ssh.spec)
            ndim = max(len(pe), len(se))
            pe += [None] * (ndim - len(pe))
            se += [None] * (ndim - len(se))
            plan = None
            for d in range(ndim):
                pa, sa = _entry_axes(pe[d]), _entry_axes(se[d])
                if tuple(sa) != tuple(pa):
                    if tuple(sa[: len(pa)]) != tuple(pa):
                        raise ValueError(
                            f"state spec {se} does not extend param spec {pe}")
                    plan = (d, tuple(sa[len(pa):]))
                    break
            _kernel_plan[jax.tree_util.keystr(ppath)] = plan

    if model.lora_config is not None:
        # LoRA: state.params is the adapter tree; the step builds full params
        # from it so loss_fn is unchanged, and differentiates w.r.t. the
        # adapters only — the base (closed over) gets no gradient, no
        # optimizer state, and cannot drift (reference requires_grad freeze,
        # modules/lora/model.py:175). With dropout the adapters are ATTACHED
        # (in-activation dropout(x)@A@B inside the layers — exact reference
        # semantics, lora/layer.py:178-179); otherwise merged into W.
        inner_loss = loss_fn
        lora_cfg = model.lora_config

        def loss_fn(lora_tree, batch, rng):  # noqa: F811
            if lora_cfg.lora_dropout > 0.0:
                from neuronx_distributed_tpu.lora.core import attach_adapters

                drop_rng, rng = jax.random.split(rng)
                params = attach_adapters(
                    model.params, lora_tree, lora_cfg, drop_rng)
            else:
                params = model.merged_params(lora_tree)
            return inner_loss(params, batch, rng)

    def step_fn(state: TrainState, batch: PyTree, rng: jax.Array):
        grad_fn = jax.value_and_grad(loss_fn)
        if grad_accum_steps > 1:
            lead = jax.tree.leaves(batch)[0].shape[0]
            if lead % grad_accum_steps:
                raise ValueError(
                    f"batch leading dim {lead} not divisible by "
                    f"grad_accum_steps={grad_accum_steps}")
            micro = jax.tree.map(
                lambda x: x.reshape(grad_accum_steps,
                                    x.shape[0] // grad_accum_steps,
                                    *x.shape[1:]),
                batch)

            def accum(carry, mb_rng):
                loss_acc, grads_acc = carry
                mb, r = mb_rng
                loss_i, grads_i = grad_fn(state.params, mb, r)
                with jax.named_scope("grad_accumulate"):
                    return (loss_acc + loss_i.astype(jnp.float32),
                            jax.tree.map(
                                lambda a, g: a + g.astype(jnp.float32),
                                grads_acc, grads_i)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, grads32), _ = jax.lax.scan(
                accum, (jnp.float32(0.0), zeros),
                (micro, jax.random.split(rng, grad_accum_steps)))
            with jax.named_scope("grad_accumulate"):
                loss = loss / grad_accum_steps
                grads = jax.tree.map(
                    lambda g, p: (g / grad_accum_steps).astype(p.dtype),
                    grads32, state.params)
        else:
            loss, grads = grad_fn(state.params, batch, rng)
        metrics = {"loss": loss}
        fused = hasattr(optimizer.tx, "update_and_params")
        scale = None
        if optimizer.grad_clipping:
            with jax.named_scope("grad_clip"):
                if fused:
                    # fused path: compute the norm (one read pass) but fold
                    # the clip SCALE into the optimizer's grad cast — the
                    # clipped grad tree is never written to HBM
                    from neuronx_distributed_tpu.parallel.grads import (
                        get_grad_norm,
                    )

                    grad_norm = get_grad_norm(grads)
                    # same coefficient as clip_grads_with_norm (grads.py);
                    # the scale is applied in the optimizer's fp32 grad cast,
                    # skipping the classic path's bf16 round-trip of the
                    # scaled grads
                    scale = jnp.clip(
                        optimizer.max_grad_norm / (grad_norm + 1e-6), max=1.0)
                else:
                    grads, grad_norm = clip_grad_norm(
                        grads, optimizer.max_grad_norm)
            metrics["grad_norm"] = grad_norm
        with jax.named_scope("optimizer_update"):
            if fused and use_kernel:
                # single-pass Pallas kernel per leaf, under shard_map (GSPMD
                # cannot partition a pallas_call): every device updates its own
                # STATE shard. ZeRO-1 state is more sharded than the params, so
                # the wrapper performs the operational ZeRO dataflow explicitly:
                # slice this device's state-shard of the (replicated-over-DP)
                # grads, update, then all-gather the new param shards back to
                # the param layout — the same reduce-scatter/all-gather schedule
                # GSPMD derives on the declarative path.
                specs_p = jax.tree.map(lambda s: s.spec, param_shardings)
                specs_s = jax.tree.map(lambda s: s.spec, opt_shardings)

                def to_state_shard(path, g):
                    plan = _kernel_plan.get(jax.tree_util.keystr(path))
                    if plan is None:
                        return g
                    d, axes = plan
                    n, idx = 1, jnp.int32(0)
                    for ax in axes:
                        n *= jax.lax.axis_size(ax)
                        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
                    shard = g.shape[d] // n
                    return jax.lax.dynamic_slice_in_dim(g, idx * shard, shard, d)

                def to_param_shard(path, p):
                    plan = _kernel_plan.get(jax.tree_util.keystr(path))
                    if plan is None:
                        return p
                    d, axes = plan
                    return jax.lax.all_gather(p, axes, axis=d, tiled=True)

                def local_update(g, s, p, sc):
                    g = jax.tree_util.tree_map_with_path(to_state_shard, g)
                    p_dt = jax.tree_util.tree_map_with_path(to_state_shard, p)
                    new_p, new_s = optimizer.tx.update_and_params_local(
                        g, s, p_dt, scale=sc)
                    return jax.tree_util.tree_map_with_path(to_param_shard, new_p), new_s

                new_params, new_opt_state = jax.shard_map(
                    local_update,
                    mesh=mesh,
                    in_specs=(specs_p, specs_s, specs_p, P()),
                    out_specs=(specs_p, specs_s),
                    check_vma=False,
                )(grads, state.opt_state, state.params,
                  jnp.float32(1.0) if scale is None else scale)
            elif fused:
                new_params, new_opt_state = optimizer.tx.update_and_params(
                    grads, state.opt_state, state.params, scale=scale)
            else:
                updates, new_opt_state = optimizer.tx.update(
                    grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt_state)
        return new_state, metrics

    # Pin state shardings so ZeRO-1 state stays DP-sharded across steps and
    # params stay on their TP/EP layout; donate the old state buffers.
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()),
        params=param_shardings,
        opt_state=opt_shardings,
    )
    return jax.jit(
        step_fn,
        in_shardings=(state_shardings, None, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )
