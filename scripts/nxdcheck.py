#!/usr/bin/env python3
"""nxdcheck CLI: statically enforce the serving stack's contracts.

    python scripts/nxdcheck.py [--json] [--rules host-sync,determinism]
                               [--root PATH] [--waivers PATH]

Runs the ``neuronx_distributed_tpu.analysis`` rule engine over the repo:
host-sync-in-traced-code, cache-boundary replication, resource
pin/release pairing, determinism discipline, and fault/observability
surface drift. STDLIB-ONLY, no jax import — milliseconds
of ``ast.parse``, wired into tier-1 so a contract regression fails the
suite before a chaos run has to find it.

Output protocol: human-readable finding lines on stderr,
ONE compact JSON summary as the last stdout line (``--json`` adds the
full findings list to stdout above it). Exit 0 = clean (no unwaived
findings), 1 = unwaived findings, 2 = internal/usage error.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path


def _load_analysis(root: Path):
    """Import the analysis package STANDALONE (as ``nxd_analysis``),
    bypassing ``neuronx_distributed_tpu/__init__.py`` — the package root
    imports jax, and this checker's whole point is running without it."""
    if "nxd_analysis" in sys.modules:
        return sys.modules["nxd_analysis"]
    pkg_dir = root / "neuronx_distributed_tpu" / "analysis"
    spec = importlib.util.spec_from_file_location(
        "nxd_analysis", pkg_dir / "__init__.py",
        submodule_search_locations=[str(pkg_dir)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["nxd_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="static contract checker (exit 1 on unwaived findings)")
    ap.add_argument("--json", action="store_true",
                    help="print the full findings list as JSON on stdout")
    ap.add_argument("--root", default=None,
                    help="repo root (default: this script's parent's parent)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids (default: all)")
    ap.add_argument("--waivers", default=None,
                    help="waiver file (default: "
                         "neuronx_distributed_tpu/analysis/waivers.txt)")
    ap.add_argument("--list", action="store_true",
                    help="list rules and exit")
    args = ap.parse_args(argv)

    root = Path(args.root) if args.root else \
        Path(__file__).resolve().parent.parent
    try:
        # the rule engine always comes from THIS repo; --root only moves
        # the tree being checked (fixture mini-repos in tests)
        analysis = _load_analysis(Path(__file__).resolve().parent.parent)
    except Exception as e:  # noqa: BLE001 - import failure is an internal error
        print(f"error: cannot import analysis package: {e}", file=sys.stderr)
        return 2
    ALL_RULES, RULES_BY_ID = analysis.ALL_RULES, analysis.RULES_BY_ID
    run_checks = analysis.run_checks

    if args.list:
        for r in ALL_RULES:
            gate = " [zero-waiver]" if r.zero_waiver else ""
            print(f"{r.id}{gate}: {r.doc}")
        print(json.dumps({"rules": [r.id for r in ALL_RULES]}))
        return 0

    rules = ALL_RULES
    if args.rules:
        try:
            rules = tuple(RULES_BY_ID[rid.strip()]
                          for rid in args.rules.split(",") if rid.strip())
        except KeyError as e:
            print(f"error: unknown rule {e} (known: "
                  f"{sorted(RULES_BY_ID)})", file=sys.stderr)
            return 2
    waiver_file = (Path(args.waivers) if args.waivers
                   else root / "neuronx_distributed_tpu" / "analysis"
                   / "waivers.txt")

    t0 = time.perf_counter()
    try:
        findings = run_checks(root, rules, waiver_file=waiver_file)
    except (SyntaxError, OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    elapsed = time.perf_counter() - t0

    unwaived = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    for f in findings:
        tag = "waived" if f.waived else "FINDING"
        reason = f" (waiver: {f.waiver_reason})" if f.waived else ""
        print(f"[{tag}] {f.rule} {f.path}:{f.line} {f.qualname}: "
              f"{f.message}{reason}", file=sys.stderr)

    by_rule = {}
    for f in unwaived:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    if args.json:
        print(json.dumps({"findings": [f.as_dict() for f in findings]},
                         indent=1))
    summary = {
        "rules": [r.id for r in rules],
        "findings": len(findings),
        "unwaived": len(unwaived),
        "waived": len(waived),
        "by_rule": by_rule,
        "elapsed_s": round(elapsed, 3),
        "verdict": "clean" if not unwaived else "findings",
    }
    print(json.dumps(summary))
    return 0 if not unwaived else 1


if __name__ == "__main__":
    sys.exit(main())
