#!/usr/bin/env python3
"""Fleet-scale scheduler soak harness (ROADMAP #18): N replicas x M
virtual-clock requests through the FULL Router/ServeEngine control plane
with a host-only sim model — zero XLA executions, bounded host RSS, and
the ``router_sched_overhead_us_per_request`` scaling curve as the
deliverable.

What runs: a :class:`SimCausalLM` fleet (real page/slot accounting, no
device — inference/simlm.py) behind a :class:`Router` in streaming mode
(``keep_completions=False``, ``record_block_wall=False``, untraced), fed
by the ``synthetic_trace_stream`` generator at a configurable load factor
of the fleet's service rate. Every per-request list is bounded by
in-flight count, so the resident set must stay FLAT: the harness samples
``/proc/self/statm`` on the block loop (mirrored into the router's
``soak_rss_mb`` gauge — leak detection reads the PR 6 metrics surface)
and reports the least-squares RSS slope over the final 80% of the run
(``rss_mb_per_100k_requests`` — ~0 when nothing leaks).

The scaling curve is the acceptance gate: with the heap-backed scheduler
(inference/schedq.py) and the per-block cached placement state,
``us_per_request`` at 1M requests must sit within 3x of its 1k value —
the old O(backlog)/O(fleet) hot paths made it grow with scale.

    JAX_PLATFORMS=cpu python scripts/soak.py                    # 1M x 100
    JAX_PLATFORMS=cpu python scripts/soak.py --requests 100000
    JAX_PLATFORMS=cpu python scripts/soak.py --curve            # 1k/100k/1M
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_PAGE_BYTES = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def rss_mb() -> float:
    """Current resident set in MB (Linux /proc; falls back to ru_maxrss —
    a peak, not current — elsewhere)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_BYTES / 1e6
    except OSError:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e3


def _rss_slope_per_100k(samples: Sequence[Tuple[int, float]],
                        tail_frac: float = 0.8) -> Optional[float]:
    """Least-squares RSS slope (MB per 100k completed requests) over the
    final ``tail_frac`` of the run by request count — the leak detector:
    steady-state growth shows as a positive slope no matter how the
    allocator plateaus early."""
    if len(samples) < 3:
        return None
    max_req = samples[-1][0]
    lo = max_req * (1.0 - tail_frac)
    pts = [(r, m) for r, m in samples if r >= lo]
    if len(pts) < 3:
        pts = list(samples)
    n = len(pts)
    mx = sum(r for r, _ in pts) / n
    my = sum(m for _, m in pts) / n
    den = sum((r - mx) ** 2 for r, _ in pts)
    if den <= 0:
        return 0.0
    slope = sum((r - mx) * (m - my) for r, m in pts) / den
    return round(slope * 1e5, 4)


def run_soak(num_requests: int, replicas: int = 100, *,
             max_batch: int = 4, block_steps: int = 8,
             max_new_tokens: int = 16, prompt_lens: Sequence[int] = (6, 10),
             paged: bool = True, page_size: int = 4,
             page_pool_pages: int = 64, placement: str = "least_loaded",
             load: float = 0.8, max_queue: Optional[int] = 64,
             deadline_frac_ms: Optional[float] = None,
             shared_prefix_len: int = 0, prefix_families: int = 1,
             seed: int = 0, sample_every_blocks: Optional[int] = None,
             max_samples: int = 2048) -> dict:
    """One soak run; returns the report dict (streaming router report +
    the RSS surface). Pure host work — safe at 1M requests."""
    from neuronx_distributed_tpu.inference.replay import (
        run_router_trace,
        synthetic_trace_stream,
    )
    from neuronx_distributed_tpu.inference.router import Router
    from neuronx_distributed_tpu.inference.simlm import SimCausalLM

    vocab = 32000
    buckets = sorted({8, 16, max(prompt_lens) + shared_prefix_len})
    max_seq = max(buckets[-1] + max_new_tokens + block_steps + 1, 64)
    if paged:
        max_seq = -(-max_seq // page_size) * page_size
    lm = SimCausalLM(
        max_batch=max_batch, buckets=buckets, max_seq_len=max_seq,
        vocab_size=vocab,
        page_size=page_size if paged else 0,
        page_pool_pages=page_pool_pages if paged else 0)
    router = Router(
        lm, replicas, placement=placement, trace=False,
        keep_completions=False, record_block_wall=False,
        block_steps=block_steps, max_queue=max_queue)
    # saturating arrival rate: fleet service rate in requests/block is
    # replicas*slots / blocks-per-request; drive it at `load` of that
    blocks_per_req = max(-(-max_new_tokens // block_steps), 1) + 1
    svc_rate = replicas * max_batch / blocks_per_req
    mean_ia = 1.0 / max(svc_rate * load, 1e-9)
    trace = synthetic_trace_stream(
        num_requests, vocab, prompt_lens=tuple(prompt_lens),
        max_new_tokens=max_new_tokens, mean_interarrival_blocks=mean_ia,
        shared_prefix_len=shared_prefix_len,
        prefix_families=prefix_families,
        deadline_ms=deadline_frac_ms, seed=seed)

    # RSS sampling rides the block loop via a wrapped step_block (the
    # run_router_trace pump stays the single driver); samples mirror into
    # the router's metrics registry so leak detection is a metrics read
    samples: List[Tuple[int, float]] = []
    gauge = router.metrics.gauge("soak_rss_mb",
                                 help="resident set during the soak")
    est_blocks = max(int(num_requests / max(svc_rate, 1e-9)), 1)
    every = (sample_every_blocks if sample_every_blocks
             else max(est_blocks // max_samples, 1))
    real_step = router.step_block

    def stepped():
        more = real_step()
        if router.blocks % every == 0:
            m = rss_mb()
            gauge.set(m)
            samples.append((router._agg["completed"], m))
        return more

    router.step_block = stepped
    rss0 = rss_mb()
    t0 = time.perf_counter()
    report = run_router_trace(router, trace)
    wall_s = time.perf_counter() - t0
    rss1 = rss_mb()
    samples.append((router._agg["completed"], rss1))
    completed = report["requests_completed"]
    report.update({
        "soak": True,
        "requests": num_requests,
        "replicas": replicas,
        "load_factor": load,
        "router_sched_overhead_us_per_request": (
            round(wall_s * 1e6 / completed, 2) if completed else None),
        "rss_mb_start": round(rss0, 1),
        "rss_mb_end": round(rss1, 1),
        "rss_mb_peak": round(max(m for _r, m in samples), 1),
        "rss_mb_per_100k_requests": _rss_slope_per_100k(samples),
        "rss_samples": [(int(r), round(m, 2)) for r, m in
                        samples[:: max(len(samples) // 64, 1)]],
    })
    return report


def scaling_curve(scales: Sequence[int] = (1_000, 100_000, 1_000_000),
                  replicas: int = 100, **kw) -> dict:
    """The ROADMAP #18 deliverable: ``us_per_request`` at each scale plus
    the 1M/1k ratio (sub-linear scheduler <=> ratio ~1; the acceptance
    gate is < 3)."""
    out = {"replicas": replicas, "scales": {}}
    for n in scales:
        rep = run_soak(n, replicas=replicas, **kw)
        out["scales"][str(n)] = {
            "router_sched_overhead_us_per_request":
                rep["router_sched_overhead_us_per_request"],
            "requests_completed": rep["requests_completed"],
            "wall_s": rep["wall_s"],
            "blocks": rep["blocks"],
            "rss_mb_peak": rep["rss_mb_peak"],
            "rss_mb_per_100k_requests": rep["rss_mb_per_100k_requests"],
        }
    keys = sorted(out["scales"], key=int)
    lo = out["scales"][keys[0]]["router_sched_overhead_us_per_request"]
    hi = out["scales"][keys[-1]]["router_sched_overhead_us_per_request"]
    out["overhead_ratio_max_vs_min_scale"] = (
        round(hi / lo, 3) if lo and hi else None)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--requests", type=int, default=1_000_000)
    ap.add_argument("--replicas", type=int, default=100)
    ap.add_argument("--load", type=float, default=0.8)
    ap.add_argument("--placement", default="least_loaded",
                    choices=("least_loaded", "affinity", "round_robin"))
    ap.add_argument("--no-paged", dest="paged", action="store_false")
    ap.add_argument("--shared-prefix-len", type=int, default=0)
    ap.add_argument("--prefix-families", type=int, default=1)
    ap.add_argument("--curve", action="store_true",
                    help="run the 1k/100k/1M scaling curve instead")
    ap.add_argument("--scales", type=int, nargs="+",
                    default=[1_000, 100_000, 1_000_000])
    ap.add_argument("--out", default=None, help="write full JSON here")
    args = ap.parse_args()
    kw = dict(replicas=args.replicas, load=args.load,
              placement=args.placement, paged=args.paged,
              shared_prefix_len=args.shared_prefix_len,
              prefix_families=args.prefix_families)
    if args.curve:
        report = scaling_curve(scales=tuple(args.scales), **kw)
        headline = {
            "router_sched_overhead_us_per_request_curve": {
                k: v["router_sched_overhead_us_per_request"]
                for k, v in report["scales"].items()},
            "overhead_ratio_max_vs_min_scale":
                report["overhead_ratio_max_vs_min_scale"],
        }
    else:
        report = run_soak(args.requests, **kw)
        headline = {
            "requests_completed": report["requests_completed"],
            "router_sched_overhead_us_per_request":
                report["router_sched_overhead_us_per_request"],
            "rss_mb_peak": report["rss_mb_peak"],
            "rss_mb_per_100k_requests":
                report["rss_mb_per_100k_requests"],
        }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    print(json.dumps(headline))
    return 0


if __name__ == "__main__":
    sys.exit(main())
