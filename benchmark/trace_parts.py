#!/usr/bin/env python3
"""Device time by the program's own names: which part of the model the chip's
time goes to, from the op metadata an ``.xplane.pb`` holds (``xplane_meta``).

    python3 benchmark/trace_parts.py <file.xplane.pb> [benchmark/out/<cell>.json] [--dump]

``reduce_by_name`` returns, beside what ``trace_reduce`` returns and clipped to
the same ``bm:traced_window``, container ops (``while``, ``conditional``,
``call``) left out as there, seconds as means over the devices:

* ``part_s``: seconds by model part, per XLA module
  (``{"jit_fused_fn": {"attention": ..., "experts": ...}}``). The part comes
  from ONE data file, ``scope_parts.json`` (first matching row wins);
* ``pass_s``: the same ops split into forward / backward / recompute / none;
* ``category_hlo_s``: seconds by ``hlo_category``;
* ``flops``, ``bytes_accessed``: summed over the executed ops, per module
  (an op cut by the window's edge counts by the share of it inside);
* ``custom_call_s``, ``custom_call_n``: seconds and executions of each Mosaic
  kernel, by the last naming component of its ``tf_op`` (a ``pallas_call``'s
  ``name``) - XLA gives a ``custom-call`` no ``flops``;
* ``inherited_s``: seconds of ops the compiler made without a name, counted
  under the part of the op they read or that reads them (``inherit``);
* ``unnamed_s``: seconds of ops no part claims and no scope names. It depends
  on the compile cache's state (JAX's cache key leaves names out, so a program
  loaded from an older entry carries the older names): kept in the record,
  never a metric;
* ``op_labels``: ``{op name: "part:last/two"}`` for the ops that took the most
  time, to make ``breakdown.device_ops`` readable.

Given the record of the same run (``benchmark/out/<cell>.json``, written by
``run.py --trace 1``), the command also prints the per-layer shares in
``SHARES``. Standard library only: it can be run where jax is not.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

HERE = Path(__file__).resolve().parent
if __name__ == "__main__":
    sys.path.insert(0, str(HERE.parent))

from benchmark import trace_reduce, xplane_meta  # noqa: E402

TABLE = HERE / "scope_parts.json"
WINDOW = trace_reduce.WINDOW
MODULE_EVENT = re.compile(r"^(.*?)\s*\((\d+)\)$")
DECODE, INSERT, STEP = "jit_fused_fn", "jit_insert_fn", "jit_step_fn"


class Table(NamedTuple):
    transforms: "re.Pattern"
    structural: "re.Pattern"
    parts: List[Tuple[str, str, "re.Pattern"]]
    passes: List[Tuple[str, str, "re.Pattern"]]


def load_table(path: Path = TABLE) -> Table:
    raw = json.loads(Path(path).read_text())

    def rows(key):
        return [(name, field, re.compile(rx)) for name, field, rx in raw[key]]

    return Table(re.compile(raw["transforms"]), re.compile(raw["structural"]),
                 rows("parts"), rows("passes"))


def stack(tf_op: Optional[str], table: Table) -> List[str]:
    """The components of a ``tf_op``, primitive last: of several stacks joined
    by ``;`` (merged ops) the first, a transform's wrapper taken off the name
    it wraps (``transpose(jvp(attend))`` -> ``attend``, ``vmap()`` -> gone)."""
    found = []
    for c in (tf_op or "").split(";")[0].rstrip(":").split("/"):
        while (m := table.transforms.match(c)):
            c = m.group(1)
        if c:
            found.append(c)
    return found


def scope(tf_op: Optional[str], table: Table) -> List[str]:
    """The NAMING components: the primitive and the structural ones
    (``jit(f)``, ``while``, ``body``, ``closed_call`` ...) taken out."""
    return [c for c in stack(tf_op, table)[:-1] if not table.structural.match(c)]


def _first(rows, meta: dict, table: Table, unwrapped: bool) -> Optional[str]:
    for name, field, rx in rows:
        text = str(meta.get(field) or "")
        if field == "tf_op" and unwrapped:
            text = "/".join(stack(text, table))
        if rx.search(text):
            return name
    return None


def part_of(meta: dict, table: Table) -> str:
    return _first(table.parts, meta, table, True) or (
        "named_other" if scope(meta.get("tf_op"), table) else "unnamed")


def pass_of(meta: dict, table: Table) -> str:
    return _first(table.passes, meta, table, False) or "none"


def label(meta: dict, table: Table) -> str:
    """``experts:experts._mlp/eci,eih->ech``: the part and the last two naming
    components, where the trace has them."""
    tail = "/".join(scope(meta.get("tf_op"), table)[-2:])
    return part_of(meta, table) + (":" + tail if tail else "")


class About(NamedTuple):
    """What every execution of one op (one ``metadata_id``) shares."""
    container: bool
    module: str
    part: str
    pass_: str
    category: str
    flops: float
    nbytes: float
    name: str
    label: str
    kernel: Optional[str]          # a Mosaic kernel's pallas_call name
    lent: bool                     # named by a neighbour (``inherit``)


OPERAND = re.compile(r"%([\w.\-]+)")


def inherit(plane, table: Table) -> Dict[int, dict]:
    """``metadata_id -> the metadata to name it by`` for the ops of one device
    plane that carry no name of their own: the compiler makes them (a
    ``convert`` of the gathered slab, a ``copy-done`` that changes a weight's
    layout) and gives them no ``tf_op``, so no scope can reach them. Such an
    op takes the name of what it reads and, failing that, of what reads it,
    within its program, two steps at most (``copy-done`` <- ``copy-start`` <-
    a parameter: nothing; -> the matmul that multiplies by it)."""
    programs = defaultdict(dict)              # program_id -> op name -> metadata
    for meta in plane.metadata.values():
        if "program_id" in meta:
            programs[meta["program_id"]][trace_reduce.op_name(meta["name"])] = meta
    readers = {}                              # program_id -> op name -> metadata of its readers

    def operands(meta):
        return OPERAND.findall(meta["name"].partition(" = ")[2])

    def named(meta):                          # by its own name stack, not by its category alone
        return bool(meta.get("tf_op")) and part_of(meta, table) != "unnamed"

    def reach(meta, step, depth):
        for other in step(meta):
            if named(other):
                return other
        if depth > 1:
            for other in step(meta):
                found = reach(other, step, depth - 1)
                if found:
                    return found
        return None

    def reads(meta):
        ops = programs[meta["program_id"]]
        return [ops[o] for o in operands(meta) if o in ops]

    def read_by(meta):
        pid = meta["program_id"]
        if pid not in readers:
            found = readers[pid] = defaultdict(list)
            for other in programs[pid].values():
                for o in operands(other):
                    found[o].append(other)
        return readers[pid][trace_reduce.op_name(meta["name"])]

    out = {}
    for mid, meta in plane.metadata.items():
        if "program_id" in meta and not meta.get("tf_op") and part_of(meta, table) == "unnamed":
            source = reach(meta, reads, 2) or reach(meta, read_by, 2)
            if source:
                out[mid] = source
    return out


def reduce_by_name(data: bytes, table: Optional[Table] = None) -> Optional[dict]:
    """The reduction by name of one trace file's bytes. None without a device
    plane."""
    table = table or load_table()
    devices = xplane_meta.device_planes(data)
    if not devices:
        return None
    windows = [(a, b) for name, a, b in xplane_meta.host_spans(data, WINDOW) if name == WINDOW]
    lo, hi = windows[0] if windows else (-float("inf"), float("inf"))
    n = len(devices)
    part_s = defaultdict(lambda: defaultdict(float))
    pass_s = defaultdict(lambda: defaultdict(float))
    cat_s: Dict[str, float] = defaultdict(float)
    flops: Dict[str, float] = defaultdict(float)
    nbytes: Dict[str, float] = defaultdict(float)
    call_s: Dict[str, float] = defaultdict(float)
    call_n: Dict[str, float] = defaultdict(float)
    by_name = defaultdict(lambda: defaultdict(float))     # op name -> label -> seconds
    inherited = 0.0
    for plane in devices:
        modules = {}
        for ev in plane.lines.get(xplane_meta.MODULES_LINE, []):
            m = MODULE_EVENT.match(plane.metadata.get(ev.metadata_id, {}).get("name", ""))
            if m:
                modules[m.group(2)] = m.group(1)
        lent = inherit(plane, table)
        seen: Dict[int, About] = {}
        for ev in plane.lines.get(xplane_meta.OPS_LINE, []):
            a, b = max(ev.start_s, lo), min(ev.start_s + ev.duration_s, hi)
            if b <= a:
                continue
            about = seen.get(ev.metadata_id)
            if about is None:
                meta = plane.metadata.get(ev.metadata_id, {"name": ""})
                by = lent.get(ev.metadata_id)          # named by a neighbour: "~" marks it
                mosaic = "tpu_custom_call" in meta["name"]    # buffer allocations are custom-calls too
                about = seen[ev.metadata_id] = About(
                    container=trace_reduce.opcode(meta["name"]) in trace_reduce.CONTAINER,
                    module=modules.get(str(meta.get("program_id")), "?"),
                    part=part_of(by or meta, table), pass_=pass_of(by or meta, table),
                    category=str(meta.get("hlo_category") or "?"),
                    flops=float(meta.get("flops") or 0), nbytes=float(meta.get("bytes_accessed") or 0),
                    name=trace_reduce.op_name(meta["name"]),
                    label="~" + label(by, table) if by else label(meta, table),
                    kernel=(scope(meta.get("tf_op"), table) or ["?"])[-1] if mosaic else None,
                    lent=bool(by))
            if about.container:
                continue
            dur = b - a
            share = dur / ev.duration_s if ev.duration_s > 0 else 1.0
            part_s[about.module][about.part] += dur
            pass_s[about.module][about.pass_] += dur
            cat_s[about.category] += dur
            flops[about.module] += about.flops * share
            nbytes[about.module] += about.nbytes * share
            by_name[about.name][about.label] += dur
            if about.lent:
                inherited += dur
            if about.kernel:
                call_s[about.kernel] += dur
                call_n[about.kernel] += share
    top = sorted(by_name.items(), key=lambda kv: -sum(kv[1].values()))[:40]
    return {
        "part_s": {m: {p: s / n for p, s in d.items()} for m, d in part_s.items()},
        "pass_s": {m: {p: s / n for p, s in d.items()} for m, d in pass_s.items()},
        "category_hlo_s": {k: v / n for k, v in cat_s.items()},
        "flops": {k: v / n for k, v in flops.items()},
        "bytes_accessed": {k: v / n for k, v in nbytes.items()},
        "custom_call_s": {k: v / n for k, v in call_s.items()},
        "custom_call_n": {k: v / n for k, v in call_n.items()},
        "unnamed_s": sum(d.get("unnamed", 0.0) for d in part_s.values()) / n,
        "inherited_s": inherited / n,
        "op_labels": {name: max(labs, key=labs.get) for name, labs in top},
    }


def reduce_file(path: str) -> Optional[dict]:
    return reduce_by_name(Path(path).read_bytes())


# ------------------------------------------------------------------ shares
# Each takes the record of a traced run whose ``device_trace`` holds BOTH
# ``trace_reduce``'s keys and the keys above, and returns a number or None:
# the shape of a ``layer_metrics/<name>.py`` reader. Every one is defined on
# names a program carries whatever its cache entry's age (flax module paths,
# hlo_category, flops, bytes_accessed); the named scopes only move time
# between the parts that one share sums.

# everything under the attention module but its projections: rotary, the
# write into the pool, the gather of the slab, the attention over it
ATTENTION = ("attention", "kv_write", "kv_gather", "attend")
FFN = ("experts", "router", "ffn")


def _part_share(record: dict, module: str, parts) -> Optional[float]:
    trace = record.get("device_trace") or {}
    total = trace.get("module_s", {}).get(module)
    found = trace.get("part_s", {}).get(module)
    if not total or found is None:
        return None
    return 100.0 * sum(found.get(p, 0.0) for p in parts) / total


def decode_attention_share(record):
    return _part_share(record, DECODE, ATTENTION)


def decode_ffn_share(record):
    return _part_share(record, DECODE, FFN)


def decode_sampler_share(record):
    return _part_share(record, DECODE, ("sampler",))


def prefill_experts_share(record):
    return _part_share(record, INSERT, ("experts",))


def decode_bytes_over_needed(record):
    """``bytes_accessed`` of the fused decode's executed ops per LIVE decode
    step, over the bytes ``opcount.decode_step_bytes`` says such a step needs
    (rows, context and experts read as ``decode.roofline_share`` takes them:
    read back from it)."""
    from benchmark import decode_steps
    from benchmark.run import read_layer_metric

    moved = (record.get("device_trace") or {}).get("bytes_accessed", {}).get(DECODE)
    ran = decode_steps.traced_decode(record)
    share = read_layer_metric("decode.roofline_share", record)
    if not moved or ran is None or not share:
        return None
    need = share / 100.0 * record["peaks"]["hbm_bytes_per_s"] * ran["step_s"]
    return moved / ran["live_steps"] / need


def train_matmul_share(record):
    """Time of ops whose ``hlo_category`` names a convolution (XLA's word for a
    matmul on the TPU: ``convolution``, ``convolution fusion``) over busy."""
    trace = record.get("device_trace") or {}
    cats = trace.get("category_hlo_s")
    if not trace.get("busy_s") or cats is None:
        return None
    return 100.0 * sum(s for c, s in cats.items() if "convolution" in c) / trace["busy_s"]


def train_hw_flops_share(record):
    """FLOPs the chip executed in the step program over the program's device
    time, over the bf16 peak: XLA's count of each executed op (recomputation
    included), plus the flash kernels', which XLA cannot see into (a Mosaic
    ``custom-call`` carries no ``flops``). A kernel execution covers one
    layer's attention over this chip's share of the step's sequences and
    heads; by its matmuls over the pairs the causal mask keeps, the forward
    makes two (``opcount.attention_flops``), dK/dV four, dQ three."""
    from benchmark import opcount

    trace = record.get("device_trace") or {}
    done, busy = trace.get("flops", {}).get(STEP), trace.get("module_s", {}).get(STEP)
    if not done or not busy:
        return None
    seq = record["mix"]["seq_len"]
    sequences = record["tokens_per_step"] / seq / record["chips"]    # TP splits the heads
    forward = opcount.attention_flops(record["config"], seq) * sequences
    calls = trace.get("custom_call_n", {})
    done += forward * (calls.get("flash_fwd", 0.0) + 2.0 * calls.get("flash_bwd_dkv", 0.0)
                       + 1.5 * calls.get("flash_bwd_dq", 0.0))
    return 100.0 * done / busy / record["peaks"]["bf16_flops_per_s"]


SHARES = {
    "decode.attention_share": decode_attention_share,
    "decode.ffn_share": decode_ffn_share,
    "decode.sampler_share": decode_sampler_share,
    "decode.bytes_over_needed": decode_bytes_over_needed,
    "prefill.experts_share": prefill_experts_share,
    "train_step.matmul_share": train_matmul_share,
    "train_step.hw_flops_share": train_hw_flops_share,
}


# ------------------------------------------------------------------- by hand

def dump(data: bytes, per_line: int = 6) -> None:
    """Planes, lines, event counts and a few events with ALL their metadata."""
    for plane in xplane_meta.planes(data, all_stats=True):
        print(f"PLANE {plane.name!r}: {len(plane.lines)} lines, {len(plane.metadata)} metadata")
        for name, events in plane.lines.items():
            print(f"  LINE {name!r}: {len(events)} events")
            for ev in events[:per_line]:
                meta = {k: (v if not isinstance(v, (str, bytes)) else str(v)[:70])
                        for k, v in plane.metadata.get(ev.metadata_id, {}).items()}
                print(f"    start_s={ev.start_s:.9f} dur_s={ev.duration_s:.9f} {meta}")


def main(argv: List[str]) -> int:
    args = [a for a in argv if not a.startswith("--")]
    data = Path(args[0]).read_bytes()
    if "--dump" in argv:
        dump(data)
        return 0
    got = reduce_by_name(data)
    out = {"by_name": got}
    if len(args) > 1 and got:
        from benchmark import run as harness

        record = json.loads(Path(args[1]).read_text())["record"]
        if not record.get("device_trace"):
            print(f"trace_parts: {args[1]} is the record of a run without --trace 1", file=sys.stderr)
            return 2
        bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        cell = next(w for w in bench["workloads"] if w["name"] == record["cell"])
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        record["config"] = harness.load_config(entry, False)
        from benchmark import traffic

        record["mix"] = traffic.load_mix(cell["traffic"], False)
        record["device_trace"] = dict(record["device_trace"], **got)
        out["shares"] = {k: f(record) for k, f in SHARES.items()}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
