#!/usr/bin/env python3
"""Compile a cell's programs at their real sizes for a DESCRIBED v5e, here.

    JAX_PLATFORMS=cpu python benchmark/aot_check.py --workload <name>

No chip is attached and nothing runs: the TPU compiler refuses here what it
would refuse on the chip (a program that does not fit the 15.75 GiB, a kernel
Mosaic cannot lower), and ``memory_analysis()`` says what each program holds.
Parameters are shapes (``jax.eval_shape``) placed on the described devices.
A compile that passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def gib(n) -> float:
    return round(n / 2**30, 2)


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments_gib": gib(m.argument_size_in_bytes), "temp_gib": gib(m.temp_size_in_bytes),
            "output_gib": gib(m.output_size_in_bytes), "alias_gib": gib(m.alias_size_in_bytes),
            "total_gib": gib(m.argument_size_in_bytes + m.temp_size_in_bytes
                             + m.output_size_in_bytes - m.alias_size_in_bytes)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from flax.core import meta
    from jax.experimental import topologies

    from benchmark import run as harness
    from benchmark import traffic
    from benchmark.drivers import serving

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_config(entry, rehearse=False)
    mix = traffic.load_mix(cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[: cell["chips"]]

    from neuronx_distributed_tpu.parallel import mesh
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    model_cls = serving.load(cfg["builder"]["model"])
    out = {}

    def abstract_params(mcfg, ids):
        module = model_cls(mcfg)
        abstract = jax.eval_shape(lambda: module.init(jax.random.key(0), ids))
        specs = nn.get_partition_spec(abstract)["params"]
        shardings = specs_to_shardings(specs, mesh.get_mesh())
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                            meta.unbox(abstract)["params"], shardings)

    if mix["driver"] == "serving":
        from neuronx_distributed_tpu.inference import CausalLM
        from neuronx_distributed_tpu.inference.sampling import SlotSampler

        mesh.initialize_model_parallel(tensor_model_parallel_size=1, devices=devices)
        # the fused decode commits its example row arrays to the mesh with
        # device_put, which a described device cannot take: hand it shapes
        from jax.sharding import NamedSharding, PartitionSpec

        from neuronx_distributed_tpu.inference import causal_lm, partition

        repl = NamedSharding(mesh.get_mesh(), PartitionSpec())
        as_shapes = lambda *xs: tuple(  # noqa: E731
            jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl) for x in xs)
        partition.repl_args = as_shapes
        if hasattr(causal_lm, "repl_args"):
            causal_lm.repl_args = as_shapes
        mcfg = serving.model_config(cfg, False, max_seq_len=int(mix["max_seq_len"]),
                                    remat_policy=None)
        params = abstract_params(mcfg, jnp.zeros((1, 8), jnp.int32))
        s = cfg["serving"]
        lm = CausalLM(mcfg, params, model_cls,
                      buckets=tuple(b for b in serving.BUCKET_LADDER if b < mcfg.max_seq_len),
                      max_batch=s["max_batch"], page_size=s["page_size"],
                      prefix_cache=s["prefix_cache"])
        t0 = time.perf_counter()
        lm.compile()
        out["decode"] = analysis(lm._decode)
        if serving.answers_decode(mix):
            out["session_fused_k8"] = analysis(lm.compile_session_decode_fused(8, SlotSampler(), 0))
        for bucket in serving.buckets_used(lm, mix):
            for rows in (1, lm.max_batch):
                out[f"paged_insert_r{rows}_b{bucket}"] = analysis(
                    lm._paged_insert_programs(rows, bucket))
        out["pool_gib"] = gib(lm.kv_cache_bytes()["kv_bytes"])
        out["compile_s"] = round(time.perf_counter() - t0, 1)
    else:
        from benchmark.drivers import training

        ctx = harness.Context(cell=cell, cfg=cfg, mix=mix, seed=0, seconds=0, traced=False,
                              rehearse=False, devices=devices, watch=None, peaks=None,
                              trace_dir=Path("."))
        out.update(training.aot(ctx))
    print(json.dumps({"workload": args.workload, "programs": out}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
