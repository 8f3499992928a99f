"""``engine.inserts_overlapped_share`` (PR 55), on the CPU: the reader on
hand-made records (a closed loop of one-token requests whose inserts overlap,
a program that fetches every insert where it dispatches it, a parent's record
without the counter) and what its entry in ``BENCHMARK.json`` promises."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "engine.inserts_overlapped_share"
CELL = "mixtral-8x7b.score"


def record(**stats):
    return {"config": {}, "mix": {}, "rows": [], "peaks": {}, "engine": {}, "chips": 1,
            "engine_stats": {"inserts": 960, "insert_host_fetches": 960, **stats}}


@pytest.mark.parametrize("overlapped,want", [(959, 100 * 959 / 960), (480, 50.0), (0, 0.0)],
                         ids=["all_but_the_first", "every_other", "fetched_where_dispatched"])
def test_the_share_by_hand(overlapped, want):
    assert harness.read_layer_metric(METRIC, record(inserts_overlapped=overlapped)) == pytest.approx(want)
    assert 0 <= want <= 100


@pytest.mark.parametrize("lacks", ["the counter", "no insert yet", "inserts", "engine_stats"])
def test_the_reader_is_silent_on_a_program_without_what_it_reads(lacks):
    """None, never a raise: the parent's record (no ``inserts_overlapped``), a
    window in which no insert ran, a driver that keeps no engine."""
    rec = record(inserts_overlapped=7)
    if lacks == "the counter":
        del rec["engine_stats"]["inserts_overlapped"]
    elif lacks == "no insert yet":
        rec["engine_stats"].update(inserts=0, inserts_overlapped=0)
    elif lacks == "inserts":
        del rec["engine_stats"]["inserts"]
    else:
        del rec["engine_stats"]
    assert harness.read_layer_metric(METRIC, rec) is None


def test_the_entry_stands_at_the_end_and_lists_the_scoring_cell():
    assert BENCH["per_layer"][-1] == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "scheduler", "moves": "tokens_per_s", "workloads": [CELL]}
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in e2e["workloads"]
    assert "scheduler" in {m["layer"] for m in BENCH["per_layer"][:-1]}       # a layer the file names
    assert METRIC in {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert (ROOT / "benchmark" / "layer_metrics" / f"{METRIC}.py").is_file()
    assert (ROOT / "BENCHMARK.json").read_text().endswith("}\n")


def test_the_engine_keeps_the_counters_the_reader_reads():
    """``engine_stats`` is ``engine.stats`` whole: the keys are registered when
    an engine is built, so a record carries them from its first insert."""
    from neuronx_distributed_tpu.inference import engine

    assert {"inserts", "inserts_overlapped", "insert_fetches_deferred",
            "slots_released_at_dispatch"} <= set(engine._STAT_KEYS)
