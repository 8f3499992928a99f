#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that names a cell, a configuration, a traffic mix or a metric is
data: ``BENCHMARK.json`` at the root names them, ``configs/<config>.json``,
``traffic/<mix>.json`` and ``layer_metrics/<metric>.py`` hold them, and this
file finds them by those names and holds none itself. The mix's ``driver``
names the module under ``drivers/`` that runs it.

The run refuses to start unless JAX's first device is a TPU whose
``device_kind`` is in ``peaks.json`` and there are as many chips as the cell
asks for. It builds the weights on the device from ``--seed``, warms up the
cell's own shapes, checks correctness against the plain reference (all of that
is set-up), measures for ``--seconds`` and prints ONE JSON object as the last
line of stdout: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced). With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics. Everything else
goes on earlier lines and into ``benchmark/out/<cell>.json``.

``--rehearse`` runs the same control flow at the configuration's tiny
rehearsal widths on host devices, names the device ``cpu`` and prints counts
only: no timing under any metric's name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # as near to process start as Python gets

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import importlib         # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = HERE / "out"


class BenchmarkFailure(Exception):
    """The run cannot give a result: say why, exit non-zero, print none."""


class CompileWatch:
    """Counts XLA backend compilations and persistent-cache hits and misses
    (copied from ``chip_smoke.py``), and keeps what the event says of each."""

    def __init__(self, jax):
        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
            self.seen.append({"s": round(secs, 3), **{k: str(v) for k, v in kw.items()}})

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def counts(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses, "compile_s": round(self.compile_s, 3)}


@dataclasses.dataclass
class Context:
    """What a driver is given. ``cfg`` is the configuration file's dict with
    the rehearsal's overrides applied when rehearsing."""
    cell: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    traced: bool
    rehearse: bool
    devices: list
    watch: CompileWatch
    peaks: Optional[dict]
    trace_dir: Path

    def emit(self, phase: str, **obs) -> None:
        print(json.dumps({"phase": phase, "t": round(self.since_start(), 2), **obs},
                         default=str), flush=True)

    def since_start(self) -> float:
        return time.perf_counter() - T_PROCESS


def load_config(entry: dict, rehearse: bool, root: Path = ROOT) -> dict:
    cfg = json.loads((root / entry["file"]).read_text())
    if rehearse:
        cfg.update(cfg.get("rehearsal") or {})
    cfg["name"] = entry["name"]
    return cfg


def metrics_of(bench: dict, kind: str, cell: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def read_layer_metric(name: str, record: dict, root: Path = HERE) -> Optional[float]:
    """``layer_metrics/<name>.py``'s ``read(record)``; None = nothing to read."""
    path = root / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(record)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def place_compile_cache(jax) -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says, else
    the fixed ``<checkout>/.jax_compile_cache`` (the program's own helper
    chooses the same). Every program is kept, however quick its compile."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not given:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return given or str(ROOT / ".jax_compile_cache")


def reduce_trace(trace_dir: Path) -> Optional[dict]:
    """Reduce the profiler's raw trace and delete it (the tree the driver
    copies must not grow)."""
    from benchmark import trace_reduce

    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    try:
        return trace_reduce.reduce_file(str(files[-1])) if files else None
    finally:
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep and files:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], Path(keep) / files[-1].name)
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny widths on host devices; counts only")
    args = parser.parse_args(argv)

    if not (ROOT / "neuronx_distributed_tpu").is_dir():
        print("benchmark: the program under test (neuronx_distributed_tpu/) is not in "
              f"{ROOT}; nothing to measure", file=sys.stderr)
        return 3
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r}; have {sorted(cells)}", file=sys.stderr)
        return 3
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])

    import jax

    found = jax.devices()
    platform = found[0].platform
    peaks_table = json.loads((HERE / "peaks.json").read_text())
    if args.rehearse:
        if platform == "tpu":
            print("benchmark: --rehearse is for host devices", file=sys.stderr)
            return 2
        peaks = None
    else:
        kind = found[0].device_kind
        if platform != "tpu" or kind not in peaks_table:
            print(f"benchmark: JAX found {len(found)} {platform} device(s) of kind {kind!r}; "
                  "this needs a TPU whose device_kind is in benchmark/peaks.json "
                  "(--rehearse runs tiny widths on the host)", file=sys.stderr)
            return 2
        peaks = peaks_table[kind]
    if len(found) < cell["chips"]:
        print(f"benchmark: {cell['name']} asks for {cell['chips']} chip(s), JAX found "
              f"{len(found)}", file=sys.stderr)
        return 2
    devices = found[: cell["chips"]]

    from benchmark import traffic

    # a rehearsal keeps no cache: host programs are tiny and another machine's
    # CPU entries only make noise
    cache_dir = None if args.rehearse else place_compile_cache(jax)
    watch = CompileWatch(jax)
    cfg = load_config(entry, args.rehearse)
    mix = traffic.load_mix(cell["traffic"], args.rehearse)
    trace_dir = OUT / "trace" / cell["name"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=cell, cfg=cfg, mix=mix, seed=args.seed, seconds=seconds,
                  traced=bool(args.trace), rehearse=args.rehearse, devices=devices,
                  watch=watch, peaks=peaks, trace_dir=trace_dir)
    ctx.emit("start", workload=cell["name"], seed=args.seed, seconds=seconds,
             trace=args.trace, rehearse=args.rehearse, platform=platform,
             kind=devices[0].device_kind, chips=len(devices), jax=jax.__version__,
             compile_cache=cache_dir)

    driver = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    try:
        record = driver.run(ctx)
    except BenchmarkFailure as e:
        print(f"benchmark: {cell['name']}: {e}", file=sys.stderr)
        return 1

    # --- from the record to the one line --------------------------------
    record.update(cell=cell["name"], config=cfg, mix=mix, seconds=seconds, seed=args.seed,
                  peaks=peaks, chips=len(devices), cache=watch.counts())
    if args.trace and not args.rehearse:
        record["device_trace"] = reduce_trace(trace_dir)
        if not record["device_trace"] or record["device_trace"]["busy_s"] <= 0:
            print(f"benchmark: {cell['name']}: the traced window holds no device "
                  "operation", file=sys.stderr)
            return 1
    device = {"platform": "cpu" if args.rehearse else platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak(devices)}
    result: dict = {"correct": bool(record["correct"]), "attempted": int(record["attempted"]),
                    "failed": int(record["failed"]), "metrics": {}, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
        result["counts"] = record.get("counts", {})
        wanted = metrics_of(bench, "per_layer" if args.trace else "end_to_end", cell["name"])
        result["would_report"] = [m["name"] for m in wanted]
    elif args.trace:
        trace = record["device_trace"]
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = read_layer_metric(m["name"], record)
            if value is None:
                # left out of the line, as the contract says; the driver holds
                # the line to every metric the cell lists, so say which is gone
                print(f"benchmark: {cell['name']}: {m['name']} found nothing to read and is "
                      "left out of the line", file=sys.stderr)
                continue
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    else:
        e2e = dict(record["end_to_end"], setup_s=record["setup_s"])
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if e2e.get(m["name"]) is None:
                print(f"benchmark: {cell['name']}: no value for {m['name']} "
                      "(too few samples in the window?)", file=sys.stderr)
                return 1
            result["metrics"][m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    # each number compared beside its limit: last on the line and last on stderr
    result["compared"] = {name: {"value": float(value), "limit": float(limit)}
                          for name, (value, limit) in (record.get("compared") or {}).items()}
    OUT.mkdir(exist_ok=True)
    slim = {k: v for k, v in record.items() if k not in ("host_spans", "config", "mix")}
    (OUT / f"{cell['name']}.json").write_text(json.dumps(
        {"result": result, "record": slim}, default=str))
    ctx.emit("done", wall_s=round(ctx.since_start(), 1), **watch.counts())
    for name, pair in result["compared"].items():
        print(f"benchmark: compared {name} {pair['value']} limit {pair['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    # run as ``benchmark.run``, the module the drivers import: one ``Context``,
    # one ``BenchmarkFailure`` and one clock, not a second copy named __main__
    sys.path.insert(0, str(ROOT))
    from benchmark.run import main as run_main

    sys.exit(run_main())
