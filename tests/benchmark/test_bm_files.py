"""BENCHMARK.json against the files it names, and the data-driven promise:
a configuration, a mix, a cell and a per-layer metric are added as FILES."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_the_file_has_exactly_the_contracts_keys_and_legal_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[kind]}) == len(BENCH[kind])
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and NAME.match(w["traffic"])
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher") and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_cell_reports_setup_another_metric_and_a_layer_metric_that_moves_it():
    for cell in CELLS:
        e2e = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_of(BENCH, "per_layer", cell)
        assert layer and all(m["moves"] in e2e for m in layer), cell


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_gives_source_sizes_and_cuts(entry):
    assert any((ROOT / entry["file"]).is_relative_to(ROOT / p) for p in BENCH["paths"])
    cfg = harness.load_config(entry, rehearse=False)
    assert cfg["source"] == entry["source"] and cfg["deployment"] and cfg["assumed"]
    published = cfg["published"]
    changed = {k for k, v in published.items() if k in cfg and cfg[k] != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"])
    width = re.compile(r"_size$|head_dim|_dim$|_rank$|experts_per_tok|latent|expansion")
    assert not any(width.search(k) for k in entry["reduced"])
    # every size the builder maps is in the file, and a reference stands beside it
    assert all(src in cfg for src in cfg["builder"]["fields"].values())
    assert (ROOT / "benchmark" / "reference" / f"{cfg['reference']['module']}.py").exists()
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_names_a_configuration_and_a_mix_that_load(cell):
    assert any(c["name"] == cell["config"] for c in BENCH["configs"])
    mix = traffic.load_mix(cell["traffic"])
    assert (ROOT / "benchmark" / "drivers" / f"{mix['driver']}.py").exists()


OPEN_LOOP = [w for w in BENCH["workloads"] if traffic.load_mix(w["traffic"]).get("loop") == "open"]


@pytest.mark.parametrize("cell", OPEN_LOOP, ids=lambda w: w["name"])
def test_open_loop_window_holds_the_samples_its_tail_metrics_need(cell):
    """The driver refuses a traced line that lacks a metric the cell lists, so
    a tail is listed only where the mix gives ten samples beyond it in every
    window: requests for ``ttft_ms_p90``, deliveries (one a block of 8 steps
    after the first token) for ``engine.delivery_gap_ms_p99``. The generator is
    stratified, so the counts are the same for every seed."""
    import numpy as np

    from benchmark import metrics

    reqs = traffic.open_loop(traffic.load_mix(cell["traffic"]), 1000, seed=7,
                             seconds=float(BENCH["run_seconds"]))
    want = np.asarray([r.max_new_tokens for r in reqs])
    samples = {"ttft_ms_p90": (want.size, 90),
               "engine.delivery_gap_ms_p99": (int(np.ceil((want - 1) / 8).sum()), 99)}
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell["name"])}
    for name in listed & set(samples):
        n, q = samples[name]
        assert metrics.percentile_with_room(np.arange(n), q) is not None, (name, n)


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_layer_metric_reader_loads_and_reads_nothing_from_an_empty_record(name):
    empty = {"mix": {}, "config": {}, "rows": [], "peaks": {}, "engine": {}, "chips": 1}
    assert harness.read_layer_metric(name, empty) is None


def test_run_py_holds_no_name_of_a_cell_config_mix_or_metric():
    text = (ROOT / "benchmark" / "run.py").read_text()
    data = (CELLS + [c["name"] for c in BENCH["configs"]] + LAYER_METRICS
            + [w["traffic"] for w in BENCH["workloads"]]
            + [m["name"] for m in BENCH["end_to_end"] if m["name"] != "setup_s"])
    assert not [n for n in data if n in text]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run([sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
                          CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
                         capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "needs a TPU" in got.stderr


def test_new_config_mix_cell_and_layer_metric_are_files_and_entries_only(tmp_path):
    """A temp copy of the benchmark gains one of each as NEW files and NEW
    entries of BENCHMARK.json; the copy's untouched run.py rehearses the new
    cell, reads the new metric, and names both in what it would report."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(ROOT / "neuronx_distributed_tpu", tmp_path / "neuronx_distributed_tpu")
    before = (tmp_path / "benchmark" / "run.py").read_bytes()
    bench = json.loads(json.dumps(BENCH))

    cfg = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3.json").read_text())
    cfg["serving"]["max_batch"] = 2
    (tmp_path / "benchmark/configs/another-7b.json").write_text(json.dumps(cfg))
    (tmp_path / "benchmark/traffic/two-callers.json").write_text(json.dumps({
        "driver": "serving", "what": "two callers, one token each", "loop": "closed",
        "clients": 2, "max_seq_len": 256, "limits": None, "drain_s": 30,
        "prompt_tokens": [{"dist": "uniform", "min": 20, "max": 60}],
        "answer_tokens": [{"dist": "fixed", "value": 1}], "shared_prefix": None}))
    (tmp_path / "benchmark/layer_metrics/engine.inserts.py").write_text(
        "def read(record):\n    return record['engine_stats']['inserts']\n")
    bench["configs"].append({"name": "another-7b", "source": cfg["source"], "reduced":
                             ["num_hidden_layers"], "why": "test",
                             "file": "benchmark/configs/another-7b.json"})
    bench["workloads"].append({"name": "another-7b.two", "config": "another-7b",
                               "traffic": "two-callers", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "engine.inserts", "unit": "inserts", "better": "higher",
                               "source": "program_counter", "layer": "scheduler",
                               "moves": "tokens_per_s", "workloads": ["another-7b.two"]})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"].append(
        "another-7b.two")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    got = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "another-7b.two",
                          "--seed", "1", "--seconds", "1", "--trace", "1", "--rehearse"],
                         capture_output=True, text=True, env=env, cwd=tmp_path, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["attempted"] > 0 and line["failed"] == 0
    assert "engine.inserts" in line["would_report"] and line["metrics"] == {}
    assert (tmp_path / "benchmark" / "run.py").read_bytes() == before
    record = json.loads((tmp_path / "benchmark/out/another-7b.two.json").read_text())["record"]
    record["engine_stats"]["inserts"]  # the new reader's input is in the record
    sys.path.insert(0, str(tmp_path))
    try:
        assert harness.read_layer_metric("engine.inserts", record,
                                         root=tmp_path / "benchmark") > 0
    finally:
        sys.path.remove(str(tmp_path))


# ------------------------------------------- the open-loop mixes, since PR 34

OPEN_MIXES = sorted({w["traffic"] for w in OPEN_LOOP})


@pytest.mark.parametrize("name", OPEN_MIXES)
def test_open_loop_mix_states_a_rate_its_own_sweep_gives(name):
    """``rate_per_s`` is 0.8 x ``knee_per_s``; where several configurations run
    the file, ``knees_per_s`` gives each one's knee, ``knee_per_s`` is the
    lowest and ``rate_bound`` names whose it is."""
    mix = traffic.load_mix(name)
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"], rel=1e-6)
    knees = mix.get("knees_per_s")
    if knees:
        assert mix["knee_per_s"] == min(knees.values())
        assert knees[mix["rate_bound"]] == mix["knee_per_s"]
        configs = {w["config"] for w in OPEN_LOOP if w["traffic"] == name}
        assert set(knees) == configs
    assert set(mix["limits"]) == {"ttft_ms", "tpot_ms"} and all(v > 0 for v in mix["limits"].values())
    assert "PR 34" in mix["swept"] and "0.8 x" in mix["swept"]


@pytest.mark.parametrize("name", OPEN_MIXES)
def test_open_loop_window_holds_enough_requests_to_judge_a_median(name):
    """A chat window holds at least 100 requests and the long-context one at
    least 40 (18 before PR 34): the judged statistic is taken over them."""
    mix = traffic.load_mix(name)
    n = round(mix["rate_per_s"] * BENCH["run_seconds"])
    assert n >= (40 if mix["max_seq_len"] > 1024 else 100), n
    reqs = traffic.open_loop(mix, 1000, seed=3, seconds=float(BENCH["run_seconds"]))
    assert len(reqs) == n


@pytest.mark.parametrize("name", OPEN_MIXES)
def test_the_sweeps_rule_is_what_the_mix_records(name):
    """Engine options and lengths are as before PR 34: only rate, knee, limits
    and ``swept`` moved."""
    mix = traffic.load_mix(name)
    assert mix["loop"] == "open" and mix["arrivals"] == {"process": "poisson"}
    assert mix["drain_s"] in (30, 60) and mix["trace_s"] in (6, 8)
    assert "knee = highest rate" in mix["swept"].lower()
    assert mix["rehearsal"]["rate_per_s"] > 0


def test_the_dense_control_runs_the_moe_cells_file_letter_for_letter():
    by_name = {w["name"]: w for w in BENCH["workloads"]}
    assert by_name["mistral-7b-v0.3.chat"]["traffic"] == by_name["mixtral-8x7b.chat"]["traffic"]
    assert by_name["mistral-7b-v0.3.chat"]["config"] != by_name["mixtral-8x7b.chat"]["config"]


@pytest.mark.parametrize("cell", OPEN_LOOP, ids=lambda w: w["name"])
def test_an_open_loop_cells_why_states_its_rate(cell):
    mix = traffic.load_mix(cell["traffic"])
    assert f"{mix['rate_per_s']:g}/s" in cell["why"], cell["why"]
    assert "all experts" not in cell["why"] and "reads all" not in cell["why"]


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"], ids=lambda c: c["name"])
def test_no_why_describes_the_program_before_the_grouped_expert_matmul(entry):
    for stale in ("all-experts", "runs all experts", "8 x the expert FLOPs", "4 x the needed"):
        assert stale not in entry["why"], entry["why"]


# ------------------------------- the judged time per token: as before PR 34

def test_the_median_time_per_token_is_judged_in_the_four_open_loop_cells_and_no_other():
    """PR 34 re-swept the rates and left ``end_to_end`` as it was: the median
    over requests under 4 %, the four open-loop cells, and the per-layer
    metrics of those cells move it."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    four = sorted(w["name"] for w in OPEN_LOOP)
    assert len(four) == 4 and sorted(e2e["tpot_ms_p50"]["workloads"]) == four
    assert e2e["tpot_ms_p50"]["bound"] == 0.04 and e2e["tpot_ms_p50"]["source"] == "host_clock"
    assert (e2e["tpot_ms_p50"]["unit"], e2e["tpot_ms_p50"]["better"]) == ("ms", "lower")
    assert set(e2e) == {"tpot_ms_p50", "tokens_per_s", "setup_s"}
    assert "tpot_ms_p50" not in {m["name"] for m in BENCH["per_layer"]}
    assert {m["moves"] for m in BENCH["per_layer"]} == set(e2e)
    assert (e2e["tokens_per_s"]["bound"], e2e["setup_s"]["bound"]) == (0.015, 0.1)


@pytest.mark.parametrize("metric", ["ttft_ms_p90", "engine.delivery_gap_ms_p99"])
def test_a_tail_is_listed_where_the_window_leaves_ten_samples_beyond_it(metric):
    """A 90th percentile wants 100 requests: every chat window holds them
    since PR 34 (OLMoE's held 82), the long-context one (65) does not."""
    listed = set(next(m for m in BENCH["per_layer"] if m["name"] == metric)["workloads"])
    for cell in OPEN_LOOP:
        mix = traffic.load_mix(cell["traffic"])
        n = round(mix["rate_per_s"] * BENCH["run_seconds"])
        assert (cell["name"] in listed) == (n >= 100), (cell["name"], n)
