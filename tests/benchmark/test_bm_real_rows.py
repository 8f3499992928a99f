"""``moe.insert_real_row_share`` (PR 51), on the CPU: the reader on hand-made
records (both share-holding configurations, a parent's program that hands on
every pick and a change that hands on a bound of them; silent on every other
configuration's record and on a program without the counters), and what its
entry in ``BENCHMARK.json`` promises. Two snapshots of the cells' metric sets
(``test_bm_latent.py``, ``test_bm_window.py``) cannot hold beside the new
entry; what each guarded is asserted here by name (``tests/conftest.py``)."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "moe.insert_real_row_share"
CELLS = ["laguna-s-2.1.longctx", "deepseek-v2.longctx"]
HOLD_A_SHARE = ["laguna-s-2.1", "deepseek-v2"]
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] not in HOLD_A_SHARE]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


def record(name="laguna-s-2.1", **stats):
    return {"config": config(name), "mix": {}, "rows": [], "peaks": {}, "engine": {}, "chips": 1,
            "engine_stats": {"moe_insert_assignments": 5_120, "moe_insert_rows": 40_960,
                             "moe_insert_layer_calls": 8, **stats}}


@pytest.mark.parametrize("name", HOLD_A_SHARE)
@pytest.mark.parametrize("rows,want", [
    (8 * 40_960, 100 * 40_960 / (8 * 40_960)),     # every pick handed on: the held eighth
    (8 * 10_240, 50.0),                            # a quarter of the list a pass, one pass a call
    (9 * 10_240, 100 * 40_960 / (9 * 10_240)),     # one call of eight overflowed into a second
], ids=["every_pick", "one_pass_a_call", "one_overflow"])
def test_the_share_by_hand(name, rows, want):
    rec = record(name, moe_insert_assignments=40_960, moe_insert_rows=rows)
    assert harness.read_layer_metric(METRIC, rec) == pytest.approx(want)
    assert 0 < want <= 100


@pytest.mark.parametrize("other", OTHERS)
def test_the_reader_is_silent_where_every_routed_expert_is_held(other):
    rec = record(other)
    assert "router_experts" not in rec["config"]
    assert harness.read_layer_metric(METRIC, rec) is None


@pytest.mark.parametrize("lacks", ["every counter", "moe_insert_rows", "moe_insert_assignments",
                                   "no insert yet", "engine_stats"])
def test_the_reader_is_silent_on_a_program_without_what_it_reads(lacks):
    """None, never a raise: a record of a program without the counters, of a
    window in which no insert ran, of a driver that keeps no engine."""
    rec = record()
    if lacks == "every counter":
        rec["engine_stats"] = {"blocks": 3}
    elif lacks == "no insert yet":
        rec["engine_stats"].update(moe_insert_rows=0, moe_insert_assignments=0)
    elif lacks == "engine_stats":
        del rec["engine_stats"]
    else:
        del rec["engine_stats"][lacks]
    assert harness.read_layer_metric(METRIC, rec) is None


def test_the_entry_stands_at_the_end_and_lists_the_cells_that_hold_a_share():
    last = BENCH["per_layer"][-1]
    assert last == {"name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
                    "layer": "model programs", "moves": "tpot_ms_p50", "workloads": CELLS}
    held = {w["name"] for w in BENCH["workloads"]
            if "router_experts" in config(w["config"])}
    assert held == set(CELLS)
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "tpot_ms_p50")
    assert set(CELLS) <= set(e2e["workloads"])
    assert (ROOT / "benchmark" / "layer_metrics" / f"{METRIC}.py").is_file()


# --------- what the two snapshots guarded, for the metrics that were there

def test_deepseeks_cell_reports_what_it_did_and_the_new_share():
    """``test_bm_latent.py::test_the_new_metrics_list_the_new_cell_only``
    without the set's closure: the cell's metrics are PR 37's and this one."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("decode.latent_roofline_share", "moe.local_assignment_share"):
        assert by_name[name]["workloads"] == ["deepseek-v2.longctx"]
        assert (by_name[name]["moves"], by_name[name]["layer"], by_name[name]["unit"]) == \
            ("tpot_ms_p50", "model programs", "%")
    for name in ("decode.roofline_share", "moe.experts_touched_share",
                 "moe.rows_per_touched_expert"):
        assert "deepseek-v2.longctx" not in by_name[name]["workloads"]
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", "deepseek-v2.longctx")}
    assert listed == {"ttft_ms_p50", "engine.host_ms_per_block", "engine.batch_occupancy",
                      "engine.slo_attainment", "decode.step_ms", "device.idle_share",
                      "cache.temp_over_pool", "cache.pool_used_peak", "setup.compile_s",
                      "setup.programs", "decode.latent_roofline_share",
                      "moe.local_assignment_share", METRIC}


def test_lagunas_cell_reports_what_it_did_and_the_new_share():
    """``test_bm_window.py::test_the_cell_reports_what_the_issue_lists_and_
    nothing_pinned_elsewhere`` the same way."""
    cell = "laguna-s-2.1.longctx"
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    swa = ["swa.decode_step_mfu_share", "swa.insert_mfu_share", "swa.window_read_over_needed"]
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)}
    assert listed == {
        "setup.compile_s", "setup.programs", "ttft_ms_p50", "engine.host_ms_per_block",
        "engine.batch_occupancy", "engine.slo_attainment", "decode.step_ms", "device.idle_share",
        "cache.temp_over_pool", "cache.pool_used_peak", "engine.admit_ms_per_block",
        "engine.observe_ms_per_block", "engine.launch_ms_per_block", "engine.harvest_ms_per_block",
        "engine.insert_stall_ms_per_block", "engine.queue_wait_ms_mean", *swa, METRIC}
    for name in listed - set(swa) - {"setup.compile_s", "setup.programs", METRIC}:
        assert by_name[name]["workloads"][-1] == cell, name          # appended, not inserted
    for name in ("decode.roofline_share", "decode.latent_roofline_share",
                 "moe.local_assignment_share"):
        assert cell not in by_name[name]["workloads"], name
    assert by_name["moe.local_assignment_share"]["workloads"] == ["deepseek-v2.longctx"]
    e2e = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", cell)}
    assert e2e == {"tpot_ms_p50", "setup_s"}
