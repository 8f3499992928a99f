"""trace_reduce on the small trace recorded on the v5e, and on made-up planes."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "small_trace_1chip.xplane.pb"


def test_names_opcodes_and_categories_from_the_instruction_text():
    text = ("%while.40 = (s32[]{:T(128)}, bf16[8,1,4096]{2,0,1:T(8,128)(2,1)S(1)}) "
            "while((s32[]{:T(128)}) %tuple.139), condition=%c, body=%b")
    assert (tr.op_name(text), tr.opcode(text)) == ("while.40", "while")
    cases = {
        "%copy.156 = pred[8]{0:T(512)(128)(4,1)} copy(pred[8]{0:T(512)(128)(4,1)} %done.1)":
            ("copy.156", "copy", "other"),
        "%convolution_fusion.3 = bf16[8,4096]{1,0:T(8,128)(2,1)} fusion(bf16[8] %a)":
            ("convolution_fusion.3", "fusion", "other"),
        "%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8] %x)":
            ("all-reduce-start.3", "all-reduce-start", "collective"),
        '%custom-call.2 = bf16[8]{0} custom-call(bf16[8] %q), custom_call_target="tpu_custom_call"':
            ("custom-call.2", "custom-call", "mosaic"),
        "%psum.7 = f32[4,8]{1,0} all-reduce(f32[4,8] %x), replica_groups={}":
            ("psum.7", "all-reduce", "collective"),
        "fusion.12": ("fusion.12", "fusion", "other"),
    }
    for text, want in cases.items():
        assert (tr.op_name(text), tr.opcode(text), tr.category(text)) == want
    assert tr.module_name("jit_fused_fn(69465781745562360)") == "jit_fused_fn"


def test_union_merges_overlaps_and_nesting():
    total, merged = tr.union_seconds([(0, 10), (2, 3), (9, 12), (20, 21)])
    assert total == 13 and merged == [(0, 12), (20, 21)]


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * 1e9, duration_ns=dur_s * 1e9, stats=[])


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v) for k, v in lines.items()])


def test_reduction_of_two_made_up_chips():
    ops0 = [ev("%while.1 = () while(() %t)", 1.0, 4.0),            # spans its children
            ev("%convolution_fusion.1 = bf16[8] fusion(bf16[8] %a)", 1.0, 2.0),
            ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %g)", 3.0, 2.0),
            ev("%custom-call.1 = bf16[8] custom-call(bf16[8] %q)", 7.0, 1.0),
            ev("%fusion.9 = bf16[8] fusion(bf16[8] %a)", 11.0, 5.0)]     # after the window
    ops1 = [ev("%convolution_fusion.1 = bf16[8] fusion(bf16[8] %a)", 1.0, 1.0),
            ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %g)", 2.0, 1.0)]
    profile = NS(planes=[
        plane("/device:TPU:0", XLA_Ops=ops0,
              XLA_Modules=[ev("jit_step_fn(123)", 1.0, 4.0), ev("jit_small(7)", 7.0, 1.0)]),
        plane("/device:TPU:1", XLA_Ops=ops1, XLA_Modules=[ev("jit_step_fn(123)", 1.0, 2.0)]),
        plane("#Chip0 Misc"),
        plane("/host:CPU", python3=[ev("bm:traced_window", 0.0, 10.0), ev("bm:step_block", 0.0, 5.5),
                                    ev("bm:sleep_to_next_arrival", 5.5, 1.0), ev("other", 0, 1)]),
    ])
    got = tr.reduce_profile(profile)
    assert got["devices"] == 2 and got["window_s"] == pytest.approx(10.0)
    assert got["busy_s_per_device"] == pytest.approx([5.0, 2.0]) and got["busy_s"] == pytest.approx(3.5)
    assert got["category_s"] == pytest.approx({"other": 1.5, "collective": 1.5, "mosaic": 0.5})
    assert got["module_s"]["jit_step_fn"] == pytest.approx(3.0)
    assert got["module_calls"] == {"jit_step_fn": 1.0, "jit_small": 0.5}
    assert [n for n, _ in got["device_ops"]][:2] == ["convolution_fusion.1", "all-reduce.1"]
    assert "while.1" not in dict(got["device_ops"])
    # chip 0 idles 0-1 and 5-7 and 8-10: by what the host was doing
    gaps = dict(got["idle_gaps"])
    assert gaps["bm:step_block"] == pytest.approx(1.5)
    assert gaps["bm:sleep_to_next_arrival"] == pytest.approx(1.0)
    assert gaps["unannotated"] == pytest.approx(2.5) and got["longest_gap_s"] == pytest.approx(2.0)


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    assert tr.reduce_profile(NS(planes=[plane("/host:CPU", python3=[ev("bm:x", 0, 1)])])) is None


def test_the_trace_recorded_on_the_v5e():
    """``benchmark/record_trace.py`` on one chip: three executions of
    ``jit_bm_matmuls`` (four 2048^3 bf16 matmuls each), 5 ms sleeps between."""
    got = tr.reduce_file(str(RECORDED))
    assert got["devices"] == 1
    assert got["module_calls"] == {"jit_bm_matmuls": 3.0}
    assert got["window_s"] == pytest.approx(0.020479, rel=1e-3)
    assert got["busy_s"] == pytest.approx(0.0010832, rel=1e-3)
    assert got["module_s"]["jit_bm_matmuls"] == pytest.approx(got["busy_s"], rel=1e-3)
    assert set(got["category_s"]) == {"other"}
    # 12 matmuls of 2 * 2048^3 FLOP in 1.083 ms: 190 TFLOP/s, under the 197 of the table
    assert 185e12 < 12 * 2 * 2048 ** 3 / got["busy_s"] < 197e12
    names = [n for n, _ in got["device_ops"]]
    assert names[:4] == ["convolution_tanh_fusion", "convolution_tanh_fusion.1",
                         "convolution_tanh_fusion.2", "convolution_tanh_fusion.3"]
    gaps = dict(got["idle_gaps"])
    assert gaps["bm:sleep_to_next_arrival"] > gaps["bm:step_block"] > 0
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)


def test_the_trace_recorded_on_four_chips():
    """The same recorder on the 2x2 host: an all-reduce over the four chips
    (jax names it ``psum.7``; its opcode makes it a collective)."""
    got = tr.reduce_file(str(RECORDED.with_name("small_trace_4chip.xplane.pb")))
    assert got["devices"] == 4 and len(got["busy_s_per_device"]) == 4
    assert got["busy_s"] == pytest.approx(sum(got["busy_s_per_device"]) / 4)
    assert got["category_s"]["collective"] == pytest.approx(dict(got["device_ops"])["psum.7"])
    assert 0 < got["category_s"]["collective"] < got["busy_s"] < got["window_s"]
