"""Each per-layer reader on a small made-up record, worked by hand."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(harness.ROOT)
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


def serving_record():
    rows = [{"due": 0.0, "submitted": 0.0, "stamps": [0.2, 1.0, 1.0, 2.0], "failed": False,
             "prompt_tokens": 1000, "want": 4, "why": None},
            {"due": 0.5, "submitted": 0.6, "stamps": [2.0, 3.5], "failed": False,
             "prompt_tokens": 500, "want": 2, "why": None},
            {"due": 0.6, "submitted": 0.6, "stamps": [], "failed": True, "prompt_tokens": 10,
             "want": 2, "why": "rejected"}]
    return {
        "rows": rows, "mix": {"limits": {"ttft_ms": 1000, "tpot_ms": 700}},
        "config": config("mistral-7b-v0.3"), "peaks": PEAKS, "chips": 1,
        "traced": [0.0, 4.0],
        "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 4096},
        "engine_stats": {"decode_blocks": 10, "generated_tokens": 330, "inserted_requests": 10,
                         "inserts": 7},
        "block_spans": [(0.0, 0.10, True), (0.10, 0.30, True), (0.30, 0.31, False)],
        "host_spans": [{"name": "insert", "lane": ("engine", "dispatch"), "ts": 0.01, "dur": 0.02},
                       {"name": "decode", "lane": ("engine", "dispatch"), "ts": 0.04, "dur": 0.05},
                       {"name": "decode", "lane": ("engine", "dispatch"), "ts": 0.11, "dur": 0.17},
                       {"name": "decode_block", "lane": ("engine", "blocks"), "ts": 0.04, "dur": 0.05}],
        "pool": {"pages": 2056, "pages_in_use_peak": 1028, "prefix_hits": 0, "bytes": 2 * 2**30},
        "fused_decode_memory": {"temp_bytes": 5 * 2**30, "argument_bytes": 0, "output_bytes": 0},
        "compile": {"programs": 10, "compile_s": 12.5},
        "device_trace": {"devices": 1, "window_s": 4.0, "busy_s": 3.0,
                         "category_s": {"mosaic": 0.6, "other": 2.4, "collective": 0.0},
                         "module_s": {"jit_fused_fn": 2.4, "jit_insert_fn": 0.5},
                         # three decode blocks, fetched at 1.0, 2.0 and 3.5 (the rows' stamps)
                         "module_calls": {"jit_fused_fn": 3.0, "jit_insert_fn": 2.0}},
    }


SERVING = {
    "ttft_ms_p50": 850.0,                        # (200 + 1500) / 2, from due_s; the failed one left out
    "setup.compile_s": 12.5,
    "setup.programs": 10,
    "engine.host_ms_per_block": 30.0,            # blocks: 100 - 70 = 30 ms and 200 - 170 = 30 ms
    "engine.batch_occupancy": 100.0 * 320 / (10 * 8 * 8),
    "engine.slo_attainment": 100.0 / 3,          # only the first meets both; the failed one misses
    "decode.step_ms": 2.4 / 4 * 1e3,             # live steps: 2 + 1 + 1 of the three blocks' 24
    "prefill.ms_per_call": 250.0,
    "cache.temp_over_pool": 2.5,
    "cache.pool_used_peak": 50.0,
    "kernels.mosaic_time_share": 20.0,
    "device.idle_share": 25.0,
    "device.busy_share": 75.0,
}


@pytest.mark.parametrize("name", sorted(SERVING))
def test_serving_reader(name):
    assert harness.read_layer_metric(name, serving_record()) == pytest.approx(SERVING[name])


def test_delivery_gap_tail_needs_a_thousand_gaps():
    rec = serving_record()
    assert harness.read_layer_metric("engine.delivery_gap_ms_p99", rec) is None
    assert harness.read_layer_metric("ttft_ms_p90", rec) is None
    rec["rows"] = [dict(rec["rows"][0], stamps=[i * 0.1 for i in range(1200)])]
    assert harness.read_layer_metric("engine.delivery_gap_ms_p99", rec) == pytest.approx(100.0)


def test_roofline_shares_use_needed_bytes_and_flops():
    from benchmark import opcount

    rec = serving_record()
    cfg = rec["config"]
    # one row live in each of the 4 live steps; it read 1001 + 1002, 1003 and 501 cached tokens
    share = harness.read_layer_metric("decode.roofline_share", rec)
    step_s = 2.4 / 4
    need = opcount.decode_step_bytes(cfg, 1.0, (1001 + 1002 + 1003 + 501) / 4)
    assert share == pytest.approx(need / 819e9 / step_s * 100)
    got = harness.read_layer_metric("prefill.roofline_share", rec)
    assert got == pytest.approx(100 * opcount.prefill_flops(cfg, [1000, 500]) / 197e12 / 0.5)


def test_training_readers():
    rec = {"config": config("pythia-6.9b"), "mix": {"seq_len": 2048}, "peaks": PEAKS, "chips": 4,
           "step_ms": [500.0, 510.0, 490.0], "tokens_per_step": 16384, "rows": [],
           "device_trace": {"devices": 4, "window_s": 3.0, "busy_s": 2.7,
                            "category_s": {"collective": 0.45, "mosaic": 0.27}}}
    from benchmark import opcount

    assert harness.read_layer_metric("train_step.ms_p50", rec) == 500.0
    mfu = harness.read_layer_metric("train_step.mfu", rec)
    assert mfu == pytest.approx(100 * opcount.train_flops_per_token(rec["config"], 2048)
                                * 16384 / 0.5 / (4 * 197e12))
    assert 40 < mfu < 60
    assert harness.read_layer_metric("collectives.time_share", rec) == pytest.approx(15.0)
    assert harness.read_layer_metric("kernels.mosaic_time_share", rec) == pytest.approx(10.0)
