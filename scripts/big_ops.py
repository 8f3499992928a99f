#!/usr/bin/env python3
"""What a cell's compiled program MOVES: every op whose output is large.

    JAX_PLATFORMS=cpu python3 scripts/big_ops.py <cell> [--min-mib 6]
                                                 [--insert rows,bucket]
                                                 [--formats]

Compiles the cell's fused decode block (or, with ``--insert``, one paged
insert) at its real sizes for a DESCRIBED v5e, as ``benchmark/aot_check.py``
does: no chip is attached and nothing runs. Then reads the compiled text and
prints each op whose output is ``--min-mib`` or more, with the computation it
stands in, its shape and layout, MiB and ``op_name``, and the program's
``temp_size_in_bytes``. The compiled text's op names are the device trace's
(``fusion.1500``, ``copy.1096``), so a row of ``benchmark/trace_parts.py``
can be looked up here. The program is built the way serving builds it:
``lm.compile()`` first, whose ``decode`` settles the layout the weights are
held in (``CausalLM._ask_formats``); ``--formats`` prints the leaves that
left the default layout for it, with their ``major_to_minor`` and MiB.

What to look for (ROADMAP S10): a ``copy`` in the ENTRY computation whose
shape is a parameter's (a weight leaf held in another layout than the program
reads it in, copied whole at every call: 768 MiB a block at Mistral-7B's
widths until PR 53 held the weights the way the one-token step reads them;
what is left there is a cache leaf's, the latent pool's); in an insert, a
``dynamic-slice`` fusion and a ``copy`` of ONE layer's slice of such a leaf
inside the layer scan. Found by PR 50: a ``copy`` of a whole stacked
cache leaf inside a loop body or a ``conditional``'s branch (layout assignment
re-lays-out the operand of a ``dot_general`` whose batch dimension is not
outermost in the leaf, AHEAD of the slice that takes a layer's rows), and
``mini-gather-slice`` (an array index over the stacked rows: the whole
operand sliced into fast memory first). ``slice-start`` / ``slice-done`` of
ONE layer of a ``(periods, ...)`` weight stack is the scheduler's prefetch of
weights the matmuls need anyway.

Reads ``BENCHMARK.json`` and the cell's files; a compile that passes is not a
chip run and says nothing of time.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
          "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_CARRIES = ("parameter", "tuple", "get-tuple-element", "bitcast", "while", "conditional", "call")
_ARRAY = re.compile(r"(\w+)\[([0-9,]*)\](\{[^}]*\})?")


def big_ops(text: str, min_bytes: int) -> list:
    """``[{computation, op, kind, shape, layout, bytes, in_place, op_name}]``
    of the ops of a compiled module's text whose output (the largest array of
    a multi-output fusion) is ``min_bytes`` or more, in the text's order.
    Left out, because they move nothing of their own: the bodies of fusions
    (the fusion itself is listed), parameters, tuples and bitcasts, the
    carries of ``while`` / ``conditional`` / ``call``, and the ``-start`` of
    an asynchronous pair (its ``-done`` has the destination). ``in_place``:
    the op aliases an operand (a scatter or update of a donated leaf shows at
    the leaf's size and writes only its update)."""
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", text))
    found, computation = [], ""
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        op = _OP.match(line)
        if not op or computation in fused:
            continue
        kind = op.group(3)
        if kind in _CARRIES or kind.endswith("-start") or "ConcatBitcast" in line:
            continue
        arrays = [(math.prod(map(int, dims.split(","))) * _BYTES[dtype] if dims else _BYTES[dtype],
                   f"{dtype}[{dims}]", layout or "")
                  for dtype, dims, layout in _ARRAY.findall(op.group(2)) if dtype in _BYTES]
        if not arrays:
            continue
        size, shape, layout = max(arrays)
        if size >= min_bytes:
            name = re.search(r'op_name="([^"]*)"', line)
            found.append(dict(computation=computation, op=op.group(1), kind=kind,
                              shape=shape, layout=layout, bytes=size,
                              in_place='"aliasing_operands":{"lists":[{' in line,
                              op_name=name.group(1) if name else ""))
    return found


def described_lm(workload: str):
    """``CausalLM`` of the cell at its real sizes on one described v5e chip,
    parameters as shapes (``benchmark/aot_check.py``'s serving branch)."""
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from flax.core import meta
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import run as harness
    from benchmark import traffic
    from benchmark.drivers import serving
    from neuronx_distributed_tpu.inference import CausalLM, causal_lm, partition
    from neuronx_distributed_tpu.kernels import mode
    from neuronx_distributed_tpu.parallel import mesh
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no cell {workload!r}: " + ", ".join(w["name"] for w in bench["workloads"]))
    cfg = harness.load_config(next(c for c in bench["configs"] if c["name"] == cell["config"]),
                              rehearse=False)
    mix = traffic.load_mix(cell["traffic"])
    if mix["driver"] != "serving" or cell["chips"] != 1:
        raise SystemExit(f"{workload}: a one-chip serving cell is what this lists")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh.initialize_model_parallel(tensor_model_parallel_size=1, devices=list(topo.devices)[:1])
    repl = NamedSharding(mesh.get_mesh(), PartitionSpec())
    # a described device takes no device_put, and the process's backend is
    # the CPU: hand the programs shapes, and Mosaic (not the interpreter) the kernels
    as_shapes = lambda *xs: tuple(  # noqa: E731
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl) for x in xs)
    partition.repl_args = causal_lm.repl_args = as_shapes
    mode.interpret_kernels = lambda: False
    mcfg = serving.model_config(cfg, False, max_seq_len=int(mix["max_seq_len"]),
                                remat_policy=None)
    model_cls = serving.load(cfg["builder"]["model"])
    module = model_cls(mcfg)
    abstract = jax.eval_shape(lambda: module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    shardings = specs_to_shardings(nn.get_partition_spec(abstract)["params"], mesh.get_mesh())
    params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                          meta.unbox(abstract)["params"], shardings)
    s = cfg["serving"]
    return CausalLM(mcfg, params, model_cls,
                    buckets=tuple(b for b in serving.BUCKET_LADDER if b < mcfg.max_seq_len),
                    max_batch=s["max_batch"], page_size=s["page_size"],
                    prefix_cache=s["prefix_cache"])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workload")
    parser.add_argument("--min-mib", type=float, default=6.0)
    parser.add_argument("--insert", metavar="ROWS,BUCKET",
                        help="one paged insert in place of the fused decode block")
    parser.add_argument("--text", metavar="FILE", help="also write the compiled text there")
    parser.add_argument("--formats", action="store_true",
                        help="print the weight leaves held off the default layout")
    args = parser.parse_args(argv)

    mib = 2 ** 20
    lm = described_lm(args.workload)
    lm.compile()                    # decode first: the weights' formats are settled
    if args.formats:
        for path, leaf in lm.relaid_leaves():
            size = math.prod(leaf.shape) * leaf.dtype.itemsize
            print(f"{size / mib:8.2f} MiB  held {leaf.format.layout.major_to_minor}  "
                  f"{leaf.dtype.name}{list(leaf.shape)}  {path}")
        print(json.dumps({"workload": args.workload, "param_relaid_leaves": lm.param_relaid_leaves,
                          "param_relaid_mib": round(lm.param_relaid_bytes / mib, 1)}))
    if args.insert:
        rows, bucket = map(int, args.insert.split(","))
        compiled = lm._paged_insert_programs(rows, bucket)
    else:
        from neuronx_distributed_tpu.inference.sampling import SlotSampler

        compiled = lm.compile_session_decode_fused(8, SlotSampler(), 0)
    text = compiled.as_text()
    if args.text:
        Path(args.text).write_text(text)
    for op in big_ops(text, int(args.min_mib * mib)):
        print(f"{op['bytes'] / mib:8.2f} MiB  {op['kind'] + (' (in place)' if op['in_place'] else ''):<22} "
              f"{op['op']:<24} {op['shape']}{op['layout']}  in {op['computation']}  {op['op_name']}")
    memory = compiled.memory_analysis()
    print(json.dumps({"workload": args.workload, "program": args.insert or "session_fused_k8",
                      "temp_size_in_bytes": memory.temp_size_in_bytes,
                      "temp_mib": round(memory.temp_size_in_bytes / mib, 1),
                      "alias_mib": round(memory.alias_size_in_bytes / mib, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
