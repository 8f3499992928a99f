"""nxdcheck: the static contract checker must (a) pass clean over the
real tree (zero unwaived findings — this IS the tier-1 contract gate),
(b) keep firing on every rule's known-bad fixture, (c) stay quiet on
every rule's known-good fixture, (d) run via the CLI with its output
protocol (exit codes 0/1/2, one-line JSON summary last),
and (e) never import jax.

No jax, no model builds — this whole file is ast.parse sweeps and costs
tier-1 seconds.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from neuronx_distributed_tpu.analysis import (ALL_RULES, RULES_BY_ID,
                                              RepoCtx, run_checks)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "nxdcheck"
WAIVERS = REPO / "neuronx_distributed_tpu" / "analysis" / "waivers.txt"

RULE_IDS = ("host-sync", "cache-replication", "resource-pairing",
            "determinism", "surface-drift", "async-contract")


def _run(root, rules=ALL_RULES, waivers=None):
    return run_checks(root, rules, waiver_file=waivers)


# --------------------------------------------------------------------------
# (a) the real tree gates clean
# --------------------------------------------------------------------------

def test_full_tree_zero_unwaived_findings():
    findings = _run(REPO, waivers=WAIVERS)
    unwaived = [f for f in findings if not f.waived]
    assert unwaived == [], "\n".join(
        f"{f.rule} {f.path}:{f.line} {f.qualname}: {f.message}"
        for f in unwaived)


def test_waived_findings_carry_justifications():
    findings = _run(REPO, waivers=WAIVERS)
    for f in findings:
        if f.waived:
            assert f.waiver_reason, f"{f.path}:{f.line} waived without reason"
            # zero-waiver rules must never appear waived
            rule = RULES_BY_ID.get(f.rule)
            assert rule is None or not rule.zero_waiver


# --------------------------------------------------------------------------
# (b)+(c) per-rule fixture corpus: bad fires, good is clean
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_fires_on_known_bad(rule_id):
    findings = _run(FIXTURES / "bad", rules=(RULES_BY_ID[rule_id],))
    assert findings, f"rule {rule_id} went silent on its known-bad fixture"


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_clean_on_known_good(rule_id):
    findings = _run(FIXTURES / "good", rules=(RULES_BY_ID[rule_id],))
    assert findings == [], "\n".join(
        f"{f.rule} {f.path}:{f.line}: {f.message}" for f in findings)


# one (rule, file, needles) row per defect class the corpus encodes: each
# needle is part of the message of one bug class this repo has shipped
BAD_SHAPES = (
    ("host-sync", "traced.py", (".item()",)),
    ("cache-replication", "traced.py", ("_replicate_out", "_shard_out")),
    ("resource-pairing", "engine.py",
     ("_release_grammar", "storm", "*_pins map")),
    ("determinism", "sched.py",
     ("bare-set iteration", "wall-clock", "unseeded")),
    ("surface-drift", "faults.py", ("dead_knob_prob",)),
    ("surface-drift", "test_surface.py", ("ghost_key", "ghost_event")),
    ("async-contract", "async_loop.py",
     ("pipelined dispatch path", "harvest helpers")),
)


@pytest.mark.parametrize("rule_id,fname,needles", BAD_SHAPES,
                         ids=[f"{r}:{f}" for r, f, _ in BAD_SHAPES])
def test_bad_fixture_finding_shapes(rule_id, fname, needles):
    """Pin the SPECIFIC defect classes the corpus encodes, not just
    any-finding, each reported by its rule in its file."""
    findings = [f for f in _run(FIXTURES / "bad",
                                rules=(RULES_BY_ID[rule_id],))
                if f.path.split("/")[-1] == fname]
    assert findings, f"expected finding class absent: {(rule_id, fname)}"
    msgs = " | ".join(f.message for f in findings)
    for needle in needles:
        assert needle in msgs, f"missing defect class: {needle}"


# --------------------------------------------------------------------------
# waiver machinery
# --------------------------------------------------------------------------

def test_inline_waiver_suppresses_and_zero_waiver_rules_still_gate(tmp_path):
    pkg = tmp_path / "neuronx_distributed_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import time\n"
        "def decide():\n"
        "    # nxdcheck: waive determinism -- fixture justification\n"
        "    return time.time()\n")
    findings = _run(tmp_path)
    det = [f for f in findings if f.rule == "determinism"]
    assert len(det) == 1 and det[0].waived
    assert det[0].waiver_reason == "fixture justification"
    assert all(f.waived or f.rule == "waiver" for f in findings)

    # an empty justification is itself a finding
    (pkg / "mod.py").write_text(
        "import time\n"
        "def decide():\n"
        "    return time.time()  # nxdcheck: waive determinism\n")
    findings = _run(tmp_path)
    assert any(f.rule == "waiver" and "justification" in f.message
               for f in findings)

    # waiving a zero-waiver rule re-surfaces as a gating finding
    (pkg / "mod.py").write_text(
        "import jax\n"
        "def build(model):\n"
        "    def fn(params, cache, ids):\n"
        "        logits, mut = model.apply(params, ids)\n"
        "        # nxdcheck: waive cache-replication -- cannot waive this\n"
        "        return logits, mut['cache']\n"
        "    return jax.jit(fn)\n")
    findings = _run(tmp_path)
    gating = [f for f in findings if not f.waived]
    assert any(f.rule == "waiver" and "zero-waiver" in f.message
               for f in gating)


def test_waiver_file_format_and_matching(tmp_path):
    pkg = tmp_path / "neuronx_distributed_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import time\n"
        "def decide():\n"
        "    return time.time()\n")
    wf = tmp_path / "waivers.txt"
    wf.write_text("determinism neuronx_distributed_tpu/mod.py decide "
                  "-- fixture file waiver\n")
    findings = run_checks(tmp_path, ALL_RULES, waiver_file=wf)
    det = [f for f in findings if f.rule == "determinism"]
    assert det and all(f.waived for f in det)
    wf.write_text("this is not a valid waiver line\n")
    with pytest.raises(ValueError):
        run_checks(tmp_path, ALL_RULES, waiver_file=wf)


# --------------------------------------------------------------------------
# (d) CLI protocol + (e) no jax import
# --------------------------------------------------------------------------

def _poison_jax_env(tmp_path):
    """PYTHONPATH shim that makes `import jax` explode — the CLI passing
    under it PROVES the no-jax-import claim."""
    shim = tmp_path / "shim"
    shim.mkdir()
    (shim / "jax.py").write_text(
        "raise ImportError('nxdcheck must not import jax')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(shim)
    return env


def test_cli_clean_tree_exit0_no_jax(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "nxdcheck.py")],
        capture_output=True, text=True, env=_poison_jax_env(tmp_path),
        timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["verdict"] == "clean"
    assert summary["unwaived"] == 0
    assert set(summary["rules"]) == set(RULE_IDS)
    # the acceptance bound is < 10 s; leave headroom for a loaded box
    assert summary["elapsed_s"] < 30


def test_cli_findings_exit1_and_json(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "nxdcheck.py"),
         "--root", str(FIXTURES / "bad"), "--json"],
        capture_output=True, text=True, env=_poison_jax_env(tmp_path),
        timeout=120)
    assert proc.returncode == 1
    lines = proc.stdout.strip().splitlines()
    summary = json.loads(lines[-1])
    assert summary["verdict"] == "findings"
    assert summary["unwaived"] > 0
    full = json.loads("\n".join(lines[:-1]))
    assert {f["rule"] for f in full["findings"]} >= set(RULE_IDS)


def test_cli_usage_error_exit2(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "nxdcheck.py"),
         "--rules", "no-such-rule"],
        capture_output=True, text=True, env=_poison_jax_env(tmp_path),
        timeout=120)
    assert proc.returncode == 2


def test_analysis_package_imports_without_jax():
    src = (REPO / "neuronx_distributed_tpu" / "analysis")
    for p in src.glob("*.py"):
        text = p.read_text()
        assert "import jax" not in text, f"{p.name} imports jax"
        assert "import numpy" not in text, f"{p.name} imports numpy"


# --------------------------------------------------------------------------
# regression pins for defects the initial sweep fixed (the PR 12
# adapter-namespace precedent: the fix carries its own pin)
# --------------------------------------------------------------------------

def test_handoff_seam_carries_adapter_absence_witness():
    """The disagg handoff seam releases the grammar pin but not the
    adapter pin — legal ONLY because disagg submit rejects adapters. The
    assert is the witness; if it disappears the static gate (and, were
    the restriction relaxed, the pool-pin leak) returns."""
    eng = (REPO / "neuronx_distributed_tpu" / "inference"
           / "engine.py").read_text()
    idx = eng.index("def _handoff_group")
    body = eng[idx:idx + 4000]
    assert "assert req.request_id not in self._adapter_pins" in body
