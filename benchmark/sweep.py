#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip. Never run by the driver.

    python benchmark/sweep.py --workload <cell> --rates 1,2,3,4 [--seconds 45] [--seed 0]

One process builds the cell as ``run.py`` does (same weights, warm-up and
loop), then offers each rate for one window over a fresh engine and prints one
JSON line per rate: latency percentiles, how many requests were still
unfinished when the window closed, and the median time to first token of the
first and the last third of the requests (a backlog that grows shows as the
last third waiting longer).

Reading it (the rule ``PERF.md`` records with the numbers): the knee is the
highest rate at which the backlog does not grow and 90 % of the requests meet
both limits; the limits are twice the 90th percentiles of time to first token
and time per output token at a quarter of the knee, rounded; the cell's rate
is four fifths of the knee. Knee, rate and limits are then frozen in the
traffic file. ``--limits ttft_ms,tpot_ms`` adds the attainment under given
limits to every line.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def summary(rows, seconds: float, limits=None) -> dict:
    from benchmark import metrics

    ok = metrics.good(rows)
    ttft = np.asarray([metrics.ttft_ms(r) for r in ok if r["stamps"]])
    tpot = np.asarray([t for t in map(metrics.tpot_ms, ok) if t is not None])
    third = max(len(ttft) // 3, 1)
    out = {
        "attempted": len(rows), "failed": sum(r["failed"] for r in rows),
        "unfinished_at_close": sum(1 for r in rows
                                   if not r["stamps"] or r["stamps"][-1] > seconds),
        "ttft_ms": {q: float(np.percentile(ttft, q)) for q in (50, 90, 99)} if ttft.size else None,
        "tpot_ms": {q: float(np.percentile(tpot, q)) for q in (50, 90, 99)} if tpot.size else None,
        "ttft_ms_p50_first_third": float(np.median(ttft[:third])) if ttft.size else None,
        "ttft_ms_p50_last_third": float(np.median(ttft[-third:])) if ttft.size else None,
    }
    if limits:
        out["attainment"] = metrics.slo_attainment(rows, limits)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated requests per second")
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--limits", default=None, help="ttft_ms,tpot_ms")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    import jax

    from benchmark import run as harness
    from benchmark import traffic
    from benchmark.drivers import serving

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    seconds = args.seconds or float(bench["run_seconds"])
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("sweep: this needs a TPU (--rehearse runs tiny widths on the host)", file=sys.stderr)
        return 2
    if not args.rehearse:
        harness.place_compile_cache(jax)
    ctx = harness.Context(
        cell=cell, cfg=harness.load_config(entry, args.rehearse),
        mix=traffic.load_mix(cell["traffic"], args.rehearse), seed=args.seed, seconds=seconds,
        traced=False, rehearse=args.rehearse, devices=jax.devices()[: cell["chips"]],
        watch=harness.CompileWatch(jax), peaks=None, trace_dir=harness.OUT / "trace" / "sweep")
    limits = None
    if args.limits:
        ttft, tpot = (float(x) for x in args.limits.split(","))
        limits = {"ttft_ms": ttft, "tpot_ms": tpot}

    lm = serving.build_lm(ctx)
    lm.compile()
    engine_kw = dict(rng=jax.random.key(args.seed))
    serving.warm_up(ctx, lm, engine_kw)
    lines = []
    for rate in (float(r) for r in args.rates.split(",")):
        before = ctx.watch.counts()["compiles"]
        w = serving.measure(ctx, lm, engine_kw, seconds, traced=False, rate_per_s=rate)
        line = {"workload": cell["name"], "rate_per_s": rate, "seconds": seconds,
                "compiles_in_window": ctx.watch.counts()["compiles"] - before,
                **summary(w.rows, seconds, limits)}
        if args.rehearse:       # counts only from a host run
            line = {k: v for k, v in line.items() if "ms" not in k and k != "attainment"}
        print(json.dumps(line), flush=True)
        lines.append(line)
        del w
        gc.collect()
    harness.OUT.mkdir(exist_ok=True)
    (harness.OUT / f"sweep-{cell['name']}.json").write_text(json.dumps(lines, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
