"""Training cells: optimizer steps through the trainer API, timed by the loss.

The construction is that of ``examples/training/gpt_neox_pretrain.py``, through
the library's public entry points: ``neuronx_distributed_config`` ->
``initialize_parallel_model`` -> ``initialize_parallel_optimizer`` ->
``create_train_state`` -> ``make_train_step``. The layout (TP degree, sequence
parallelism, ZeRO-1, master weights, rematerialisation) is the configuration
file's ``training`` group.

A new seeded batch every step, put on the devices while the previous step
runs; a step is over when its loss has been fetched to the host, and the
window counts the steps whose loss arrived inside it.
"""

from __future__ import annotations

import importlib
import math
import time

import numpy as np

from benchmark import traffic
from benchmark.drivers.serving import annotate, init_rngs, load, model_config, start_profiler
from benchmark.run import BenchmarkFailure, Context


def build(ctx: Context, sample_ids):
    """(state, step, model)."""
    from neuronx_distributed_tpu.parallel import mesh
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )

    t = ctx.cfg["training"]
    tp = min(int(t["tensor_parallel_size"]), len(ctx.devices))
    mcfg = model_config(ctx.cfg, ctx.rehearse, max_seq_len=int(ctx.mix["seq_len"]),
                        sequence_parallel=bool(t["sequence_parallel"]),
                        remat_policy=t["remat_policy"])
    model_cls = load(ctx.cfg["builder"]["model"])
    mesh.initialize_model_parallel(tensor_model_parallel_size=tp, devices=ctx.devices)
    nxd = neuronx_distributed_config(
        tensor_parallel_size=tp, sequence_parallel=mcfg.sequence_parallel,
        optimizer_config={"zero_one_enabled": bool(t["zero_one"])},
        mixed_precision_config={"use_master_weights": bool(t["master_weights"])})
    model = initialize_parallel_model(nxd, lambda: model_cls(mcfg), sample_ids,
                                      rngs=init_rngs(ctx.seed))
    opt = initialize_parallel_optimizer(nxd, model, learning_rate=t["learning_rate"],
                                        weight_decay=t["weight_decay"])
    state = create_train_state(model, opt)

    def loss_fn(params, b, rng):
        return model.module.apply({"params": params}, b["ids"], b["labels"],
                                  method=model_cls.loss)

    return state, make_train_step(model, opt, loss_fn), model


def run(ctx: Context) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from neuronx_distributed_tpu.parallel import mesh

    mix = ctx.mix
    batches = traffic.train_batches(mix, ctx.cfg["vocab_size"], ctx.seed)
    first = next(batches)
    t0 = time.perf_counter()
    state, step, model = build(ctx, first["ids"])
    jax.block_until_ready(state.params)
    build_s = time.perf_counter() - t0
    where = NamedSharding(model.mesh, mesh.data_pspec())

    def put(batch):
        return jax.device_put(batch, where)

    # --- correctness, part of set-up -------------------------------------
    ref_cfg = ctx.cfg["reference"]
    reference = importlib.import_module(f"benchmark.reference.{ref_cfg['module']}")
    ref_loss = float(reference.loss(state.params, jnp.asarray(first["ids"]),
                                    jnp.asarray(first["labels"]), ctx.cfg))
    jax.clear_caches()
    on_device = put(first)
    t0 = time.perf_counter()
    state, m = step(state, on_device, jax.random.key(ctx.seed))
    loss0 = float(m["loss"])
    first_step_s = time.perf_counter() - t0
    compiles_first = ctx.watch.counts()["compiles"]
    state, m = step(state, on_device, jax.random.key(ctx.seed))
    loss1 = float(m["loss"])
    rel = abs(loss0 - ref_loss) / abs(ref_loss)
    checks = {"reference_loss": ref_loss, "loss_step0": loss0, "loss_same_batch_again": loss1,
              "relative_difference": rel, "tolerance": ref_cfg["tolerance"],
              "falls": loss1 < loss0}
    ctx.emit("reference", **checks)
    at_open = ctx.watch.counts()
    ctx.emit("setup", build_s=round(build_s, 2), first_step_s=round(first_step_s, 2),
             tp=model.mesh.shape, **at_open)

    # --- the window -------------------------------------------------------
    tokens_per_step = int(mix["global_batch"]) * int(mix["seq_len"])
    traced = ctx.traced and not ctx.rehearse
    trace_steps = int(mix.get("trace_steps", 6))
    nxt = put(next(batches))
    losses, step_ms = [], []
    i, tracing = 0, "off"
    setup_s = ctx.since_start()
    t_open = last = time.perf_counter()
    window_annotation = None
    while True:
        if tracing == "on" and i >= trace_steps + 2:
            window_annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = "done"
            break                          # the traced run measures nothing after it
        if traced and tracing == "off" and i == 2:
            start_profiler(ctx.trace_dir)
            window_annotation = jax.profiler.TraceAnnotation("bm:traced_window")
            window_annotation.__enter__()
            tracing = "on"
        with annotate("dispatch_step", traced):
            state, m = step(state, nxt, jax.random.key(ctx.seed + i + 1))
        with annotate("batch_transfer", traced):
            nxt = put(next(batches))
        with annotate("loss_fetch", traced):
            loss = float(m["loss"])        # host fetch: the step is over
        now = time.perf_counter()
        if now - t_open > ctx.seconds:
            break                          # this step's loss arrived after the close
        losses.append(loss)
        step_ms.append((now - last) * 1e3)
        last = now
        i += 1
    window = last - t_open                 # whole steps: up to the last loss inside the window
    at_close = ctx.watch.counts()
    if at_close["compiles"] != at_open["compiles"]:
        raise BenchmarkFailure(
            f"{at_close['compiles'] - at_open['compiles']} compilation(s) inside the window: "
            f"{ctx.watch.seen[at_open['compiles']:]}")
    finite = all(math.isfinite(x) for x in losses)
    correct = bool(finite and losses and rel <= ref_cfg["tolerance"] and loss1 < loss0)
    e2e = {"tokens_per_s": len(losses) * tokens_per_step / window if window > 0 else None}
    ctx.emit("window", steps=len(losses), tokens_per_step=tokens_per_step,
             losses_first_last=[losses[0], losses[-1]] if losses else None,
             all_finite=finite, compiles_in_window=0,
             end_to_end=None if ctx.rehearse else e2e,
             step_ms_p50=None if ctx.rehearse or not step_ms else float(np.median(step_ms)))
    return {
        "correct": correct, "reference": checks,
        "compared": {"loss_gap": [rel, ref_cfg["tolerance"]]},
        "attempted": len(losses), "failed": 0 if finite else sum(not math.isfinite(x) for x in losses),
        "setup_s": setup_s, "end_to_end": e2e, "step_ms": step_ms, "losses": losses,
        "tokens_per_step": tokens_per_step,
        "compile": {"programs": compiles_first, "compile_s": first_step_s, "build_s": build_s},
        "counts": {"steps": len(losses), "tokens_per_step": tokens_per_step,
                   "compiles_in_window": 0, "all_finite": finite},
    }


def aot(ctx: Context) -> dict:
    """Lower and compile the step for the described devices in ``ctx`` from
    shapes alone (``aot_check.py``): what the compiler refuses, and what the
    step holds on each device."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec

    from neuronx_distributed_tpu.parallel import mesh
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )
    from neuronx_distributed_tpu.trainer.model import ParallelModel, _apply_config_overrides

    t, mix = ctx.cfg["training"], ctx.mix
    tp = int(t["tensor_parallel_size"])
    mcfg = model_config(ctx.cfg, False, max_seq_len=int(mix["seq_len"]),
                        sequence_parallel=bool(t["sequence_parallel"]),
                        remat_policy=t["remat_policy"])
    model_cls = load(ctx.cfg["builder"]["model"])
    mesh.initialize_model_parallel(tensor_model_parallel_size=tp, devices=ctx.devices)
    nxd = neuronx_distributed_config(
        tensor_parallel_size=tp, sequence_parallel=mcfg.sequence_parallel,
        optimizer_config={"zero_one_enabled": bool(t["zero_one"])},
        mixed_precision_config={"use_master_weights": bool(t["master_weights"])})
    shape = (int(mix["global_batch"]), int(mix["seq_len"]))
    ids = jnp.zeros(shape, jnp.int32)
    module = _apply_config_overrides(model_cls(mcfg), nxd)
    abstract = jax.eval_shape(lambda: module.init(jax.random.key(0), ids))
    specs = nn.get_partition_spec(abstract)["params"]
    shardings = specs_to_shardings(specs, mesh.get_mesh())
    params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                          meta.unbox(abstract)["params"], shardings)
    model = ParallelModel(module=module, params=params, param_specs=specs, mesh=mesh.get_mesh(),
                          lora_config=None, lora_params=None, lora_specs=None)
    opt = initialize_parallel_optimizer(nxd, model, learning_rate=t["learning_rate"],
                                        weight_decay=t["weight_decay"])
    state = jax.eval_shape(lambda: create_train_state(model, opt))

    def loss_fn(p, b, rng):
        return model.module.apply({"params": p}, b["ids"], b["labels"], method=model_cls.loss)

    step = make_train_step(model, opt, loss_fn)
    where = NamedSharding(model.mesh, mesh.data_pspec())
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=where) for k in ("ids", "labels")}
    rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(model.mesh, PartitionSpec()))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, rng).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2 ** 30
    return {"train_step": {
        "per_device_arguments_gib": round(m.argument_size_in_bytes / gib, 2),
        "per_device_temp_gib": round(m.temp_size_in_bytes / gib, 2),
        "per_device_total_gib": round((m.argument_size_in_bytes + m.temp_size_in_bytes
                                       + m.output_size_in_bytes - m.alias_size_in_bytes) / gib, 2),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "collectives": {k: text.count(k + "(") + text.count(k + "-start(")
                        for k in ("all-reduce", "all-gather", "reduce-scatter",
                                  "collective-permute", "all-to-all")},
        "compile_s": round(time.perf_counter() - t0, 1)}}
