"""``decode.step_ms`` and ``decode.roofline_share`` count the steps that ran:
hand-made records, worked by hand, and the trace recorded on the v5e."""

import json
from pathlib import Path

import pytest

from benchmark import decode_steps, opcount
from benchmark import run as harness
from benchmark import trace_reduce as tr

ROOT = Path(harness.ROOT)
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
RECORDED = Path(__file__).parent / "data" / "small_trace_1chip.xplane.pb"
BW = PEAKS["hbm_bytes_per_s"]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


def row(first, blocks, prompt=100):
    """A finished request: its insert's stamp, then (stamp, tokens) per block."""
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


def record(rows, calls, module_s, cfg="mistral-7b-v0.3", traced=(10.0, 20.0), **more):
    return dict({"rows": rows, "config": config(cfg), "peaks": PEAKS, "chips": 1,
                 "traced": list(traced),
                 "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 1024},
                 "device_trace": {"devices": 1, "window_s": traced[1] - traced[0], "busy_s": module_s,
                                  "module_s": {"jit_fused_fn": module_s},
                                  "module_calls": {"jit_fused_fn": float(calls)}}}, **more)


def ends_at_step_3():
    """Two blocks in the stretch. In the first, row A runs all 8 steps and row
    B 5; in the second only row A is left and ends at step 3 of 8: 5 dead
    steps. A block before the stretch (stamp 9.0) is not counted."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=200)
    b = row(10.5, [(11.0, 5)], prompt=50)
    return record([a, b], calls=2, module_s=0.22)


def test_a_block_whose_last_row_ends_at_step_3_of_8_has_3_live_steps():
    ran = decode_steps.traced_decode(ends_at_step_3())
    assert (ran["blocks"], ran["live_steps"]) == (2, 11)
    assert ran["rows"] == pytest.approx((8 + 5 + 3) / 11)
    # A reads 209..216 then 217..219 cached tokens, B 51..55
    assert ran["context_tokens"] == pytest.approx((sum(range(209, 220)) + sum(range(51, 56))) / 11)


def test_step_ms_times_live_steps_is_the_modules_device_time():
    rec = ends_at_step_3()
    step_ms = harness.read_layer_metric("decode.step_ms", rec)
    assert step_ms == pytest.approx(0.22 / 11 * 1e3)
    assert step_ms * 11 == pytest.approx(rec["device_trace"]["module_s"]["jit_fused_fn"] * 1e3)
    # the old reading divided by all 16 steps of the two executions
    assert step_ms > 0.22 / 2 / 8 * 1e3


def test_dense_roofline_share_by_hand():
    rec = ends_at_step_3()
    ran = decode_steps.traced_decode(rec)
    need = opcount.decode_step_bytes(rec["config"], ran["rows"], ran["context_tokens"])
    got = harness.read_layer_metric("decode.roofline_share", rec)
    assert got == pytest.approx(100 * need / BW / (0.22 / 11))
    weights = (16 * 218_103_808 + 4096 * 32768) * 2
    assert need == pytest.approx(weights + ran["context_tokens"] * 65536)


def test_a_dense_block_with_no_dead_step_reads_as_the_old_count_did():
    """Where every step of every block is live the new step time IS the old
    (module time / executions / block_steps)."""
    rows = [row(9.5, [(11.0, 8), (12.0, 8), (13.0, 8)], prompt=300)]
    rec = record(rows, calls=3, module_s=0.27)
    assert harness.read_layer_metric("decode.step_ms", rec) == pytest.approx(0.27 / 3 / 8 * 1e3)


@pytest.mark.parametrize("missing", ["rows", "traced", "device_trace", "module", "a block", "stretch"])
def test_both_readers_find_nothing_on_a_record_that_lacks_what_they_read(missing):
    rec = ends_at_step_3()
    if missing == "rows":
        rec["rows"] = []
    elif missing == "traced":
        rec["traced"] = [None, None]
    elif missing == "device_trace":
        rec["device_trace"] = None
    elif missing == "module":
        rec["device_trace"]["module_calls"] = {"jit_insert_fn": 2.0}
    elif missing == "a block":             # a request that never finished leaves no stamps: the
        rec["device_trace"]["module_calls"]["jit_fused_fn"] = 3.0      # trace counts a block more
    else:                                   # no decode block was fetched in the stretch
        rec["traced"] = [30.0, 36.0]
    assert harness.read_layer_metric("decode.step_ms", rec) is None
    assert harness.read_layer_metric("decode.roofline_share", rec) is None


# ------------------------------------------------------------------- experts

def moe_record(stats=True):
    """Mixtral, two blocks of 4 rows live for all 8 steps, the second in the
    stretch. The program's counter says the window's blocks touched 5.0 of the
    8 experts a layer-step (four rows x top-2 often choose the same expert)."""
    rows = [row(9.0 + i / 10, [(9.9, 8), (11.0, 8)], prompt=100) for i in range(4)]
    rec = record(rows, calls=1, module_s=0.08, cfg="mixtral-8x7b")
    if stats:     # 3 layers x 16 steps
        rec["engine_stats"] = {"decode_blocks": 2, "moe_layer_steps": 2 * 8 * 3,
                               "moe_experts_touched": int(5.0 * 2 * 8 * 3), "moe_assignments": 384}
    return rec


def test_experts_read_come_from_the_windows_counter():
    ran = decode_steps.traced_decode(moe_record())
    assert ran["experts_per_layer_step"] == pytest.approx(5.0)


def test_dead_steps_touch_no_expert_and_do_not_dilute_the_count():
    """``moe_layer_steps`` counts ALL 8 steps of a block; a block with 4 live
    steps that touched 72 expert slots in 3 layers read 6 a live layer-step,
    not the 3 that touched / layer-steps says."""
    rows = [row(9.0, [(11.0, 4)]), row(9.1, [(11.0, 2)])]
    rec = record(rows, calls=1, module_s=0.05, cfg="mixtral-8x7b",
                 engine_stats={"decode_blocks": 1, "moe_layer_steps": 8 * 3, "moe_experts_touched": 72})
    ran = decode_steps.traced_decode(rec)
    assert ran["live_steps"] == 4 and ran["experts_per_layer_step"] == pytest.approx(6.0)


def test_a_window_whose_blocks_the_rows_do_not_all_show_gives_no_count():
    """The counter is the whole window's, so the rows must show every block
    of the window; where they do not the caller falls back to the bound."""
    rec = moe_record()
    rec["engine_stats"]["decode_blocks"] = 3
    assert decode_steps.traced_decode(rec)["experts_per_layer_step"] is None


def test_without_any_counter_the_experts_are_the_upper_bound():
    rec = moe_record(stats=False)
    assert decode_steps.traced_decode(rec)["experts_per_layer_step"] is None
    got = harness.read_layer_metric("decode.roofline_share", rec)
    need = opcount.decode_step_bytes(rec["config"], 4.0, 4 * 112.5)      # min(8, 4 x 2) = all 8
    assert got == pytest.approx(100 * need / BW / (0.08 / 8))


def test_a_reading_the_old_count_put_over_100_is_now_under_it():
    """4 rows at 10 ms a step. The old count took ``min(8, rows x 2)`` = all
    8 experts however few the rows chose; the steps read 5."""
    rec = moe_record()
    cfg = rec["config"]
    step_s = 0.08 / 8
    old = 100 * opcount.decode_step_bytes(cfg, 4.0, 4 * 112.5) / BW / step_s
    new = harness.read_layer_metric("decode.roofline_share", rec)
    assert old > 100 > new > 50
    assert new == pytest.approx(
        100 * opcount.decode_step_bytes(cfg, 4.0, 4 * 112.5, experts_read=5.0) / BW / step_s)


@pytest.mark.parametrize("read, want", [(2.0, 2.0), (5.0, 5.0), (8.0, 8.0), (9.5, 8.0)])
def test_expert_bytes_are_those_of_the_experts_read(read, want):
    cfg = config("mixtral-8x7b")
    none = opcount.decode_step_bytes(cfg, 4.0, 0.0, experts_read=0.0)
    got = opcount.decode_step_bytes(cfg, 4.0, 0.0, experts_read=read)
    assert got - none == pytest.approx(3 * want * 176_160_768 * 2)       # 3 layers, bf16


def test_a_dense_model_has_one_feed_forward_whatever_is_said_of_experts():
    cfg = config("mistral-7b-v0.3")
    assert (opcount.decode_step_bytes(cfg, 3.0, 100.0, experts_read=0.25)
            == opcount.decode_step_bytes(cfg, 3.0, 100.0))


def test_olmoe_reads_the_experts_its_rows_chose():
    cfg = config("olmoe-1b-7b")
    rows = [row(9.0, [(11.0, 8)], prompt=200), row(9.2, [(11.0, 8)], prompt=300)]
    rec = record(rows, calls=1, module_s=0.048, cfg="olmoe-1b-7b",
                 engine_stats={"decode_blocks": 1, "moe_layer_steps": 8 * 12,
                               "moe_experts_touched": 14 * 8 * 12})
    ran = decode_steps.traced_decode(rec)
    assert ran["experts_per_layer_step"] == pytest.approx(14.0)          # of the 16 two rows can choose
    got = harness.read_layer_metric("decode.roofline_share", rec)
    need = opcount.decode_step_bytes(cfg, 2.0, ran["context_tokens"], experts_read=14.0)
    assert got == pytest.approx(100 * need / BW / 0.006) and got < 100


# ------------------------------------------------------ the recorded v5e trace

def test_on_the_trace_recorded_on_the_v5e():
    """Three executions of ``jit_bm_matmuls`` stand for three decode blocks
    (the recorder's program under the decode module's name): the step time
    times the live steps is the module's device time in the trace."""
    trace = tr.reduce_file(str(RECORDED))
    name, = trace["module_calls"]
    trace["module_s"] = {"jit_fused_fn": trace["module_s"][name]}
    trace["module_calls"] = {"jit_fused_fn": trace["module_calls"][name]}
    rows = [row(-1.0, [(0.005, 8), (0.010, 8), (0.015, 4)], prompt=64),
            row(0.001, [(0.010, 2)], prompt=32)]
    rec = record(rows, calls=3, module_s=0.0, traced=(0.0, trace["window_s"]))
    rec["device_trace"] = trace
    ran = decode_steps.traced_decode(rec)
    assert (ran["blocks"], ran["live_steps"]) == (3, 20)
    step_ms = harness.read_layer_metric("decode.step_ms", rec)
    assert step_ms * 20 / 1e3 == pytest.approx(trace["module_s"]["jit_fused_fn"], rel=1e-9)
    assert step_ms == pytest.approx(1.0832 / 20, rel=1e-3)
    assert harness.read_layer_metric("decode.roofline_share", rec) > 0
