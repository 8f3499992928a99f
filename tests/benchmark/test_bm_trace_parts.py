"""The reduction by name: ``scope_parts.json``'s order, ``reduce_by_name`` on a
made-up trace worked by hand and on the traces recorded on the v5e, and each
share on a made-up record."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark import trace_parts as tp
from benchmark import trace_reduce
from tests.benchmark.test_bm_xplane_meta import (
    STATS,
    event_metadata,
    line,
    plane,
    stat,
    stat_metadata,
)

DATA = Path(__file__).parent / "data"
TABLE = tp.load_table()
DECODE = ("jit(fused_fn)/while/body/closed_call/MixtralForCausalLM/MixtralForCausalLM._hidden/model/"
          "while/body/closed_call/layers/block/")
TRAIN = "jit(step_fn)/transpose(jvp(GPTNeoXForCausalLM.loss))/GPTNeoXForCausalLM._hidden/model/while/body/"

# (tf_op, hlo_category) -> (part, pass): ISSUE 24's op_names from the chat
# cell's rehearsal and the train step's, and the scopes this PR added
CASES = {
    (DECODE + "attention/attention._decode_attention/jit(floor_divide)/rem:", "loop fusion"):
        ("attention", "none"),
    (DECODE + "attention/attention._decode_attention/bnij,bjnd->bind/dot_general:", "convolution"):
        ("attention", "none"),
    (DECODE + "attention/attention._decode_attention/kv_gather/gather:", "loop fusion"):
        ("kv_gather", "none"),
    (DECODE + "attention/attention._decode_attention/attend/bnij,bjnd->bind/dot_general:", "x"):
        ("attend", "none"),
    (DECODE + "attention/attention._decode_attention/kv_write/scatter:", "x"): ("kv_write", "none"),
    (DECODE + "attention/attention._decode_attention/attention._o_proj/o_proj/dot_general:", "x"):
        ("attn_proj", "none"),
    (DECODE + "attention/qkv/bsh,hnd->bsnd/dot_general:", "convolution fusion"): ("attn_proj", "none"),
    (DECODE + "moe/router/top_k:", "x"): ("router", "none"),
    (DECODE + "moe/experts/experts.forward_all_experts/experts._mlp/eci,eih->ech/dot_general:", "x"):
        ("experts", "none"),
    (DECODE + "post_attn_norm/rsqrt:", "x"): ("norm", "none"),
    ("jit(fused_fn)/while/body/closed_call/MixtralForCausalLM/MixtralForCausalLM._hidden/model/"
     "final_norm/mul:", "x"): ("norm", "none"),
    ("jit(fused_fn)/while/body/closed_call/MixtralForCausalLM/MixtralForCausalLM._hidden/model/"
     "embed/jit(_take)/gather:", "x"): ("embed_head", "none"),
    ("jit(fused_fn)/while/body/closed_call/MixtralForCausalLM/MixtralForCausalLM._head/lm_head/"
     "dot_general:", "x"): ("embed_head", "none"),
    ("jit(fused_fn)/while/body/closed_call/SlotSampler.__call__/reduce:", "x"): ("sampler", "none"),
    ("jit(fused_fn)/while/body/closed_call/vmap(jit(_gumbel))/jit(_uniform)/shift_right_logical:", "x"):
        ("sampler", "none"),
    ("jit(fused_fn)/while/body/closed_call/vmap(jit(_threefry_fold_in))/add:", "x"): ("sampler", "none"),
    ("jit(fused_fn)/while/body/closed_call/sampler/vmap()/add:", "x"): ("sampler", "none"),
    ("jit(fused_fn)/while/body/closed_call/bookkeeping/or:", "x"): ("bookkeeping", "none"),
    ("jit(insert_fn)/table_write/dynamic_update_slice:", "x"): ("cache_write", "none"),
    ("jit(fused_fn)/while/body/closed_call/jit(_where)/select_n:", "x"): ("unnamed", "none"),
    ("jit(fused_fn)/while/body/closed_call/vmap()/add:", "x"): ("unnamed", "none"),
    ("jit(fused_fn)/while/body/closed_call/MixtralForCausalLM/MixtralForCausalLM._hidden/model/"
     "while/body/dynamic_slice:", "x"): ("scan_carry", "none"),
    ("jit(fused_fn)/while:", "data formatting"): ("scan_carry", "none"),
    ("jit(fused_fn)/while/body/closed_call/MixtralForCausalLM/MixtralForCausalLM._hidden/model/mul:", "x"):
        ("named_other", "none"),
    # what the compiler adds and gives no name: by its category, after every row by name
    ("", "copy-done"): ("compiler_copy", "none"),
    ("", "data formatting"): ("compiler_copy", "none"),
    (DECODE + "attention/attention._decode_attention/kv_gather/copy:", "data formatting"):
        ("kv_gather", "none"),
    ("", "non-fusion elementwise"): ("unnamed", "none"),
    # a collective is a collective whatever module it is in
    (DECODE + "attention/attention._decode_attention/attention._o_proj/o_proj/psum:", "all-reduce"):
        ("collective", "none"),
    ("", "all-gather-start"): ("collective", "none"),
    # the train step: forward, backward and recomputation of the same modules
    ("jit(step_fn)/jvp(GPTNeoXForCausalLM.loss)/GPTNeoXForCausalLM._hidden/model/while/body/"
     "closed_call/layers/block/mlp/up/dot_general:", "x"): ("ffn", "forward"),
    (TRAIN + "closed_call/layers/layers/checkpoint/block/mlp/down/dot_general:", "x"): ("ffn", "backward"),
    (TRAIN + "closed_call/layers/layers/checkpoint/rematted_computation/block/attention/qkv/"
     "bsh,hnd->bsnd/dot_general:", "x"): ("attn_proj", "recompute"),
    (TRAIN + "closed_call/layers/layers/checkpoint/block/attention/flash_bwd_dq/pallas_call:",
     "custom-call"): ("attention", "backward"),
    ("jit(step_fn)/jvp(GPTNeoXForCausalLM.loss)/jit(_one_hot)/eq:", "x"): ("loss", "forward"),
    ("jit(step_fn)/jvp(GPTNeoXForCausalLM.loss)/loss/reduce_max:", "x"): ("loss", "forward"),
    ("jit(step_fn)/jvp(loss)/reduce_max:", "x"): ("loss", "forward"),
    ("jit(step_fn)/optimizer_update/mul:", "x"): ("optimizer", "none"),
    ("jit(step_fn)/grad_clip/jit(clip)/min:", "x"): ("optimizer", "none"),
    ("jit(step_fn)/jit(clip)/min:", "x"): ("unnamed", "none"),
    ("jit(step_fn)/mul:", "x"): ("unnamed", "none"),
}


@pytest.mark.parametrize("case", sorted(CASES), ids=lambda c: (c[0][-48:] or c[1]))
def test_first_matching_row_names_the_part(case):
    meta = {"tf_op": case[0], "hlo_category": case[1]}
    assert (tp.part_of(meta, TABLE), tp.pass_of(meta, TABLE)) == CASES[case]


def test_labels_keep_the_last_two_naming_components():
    moe = DECODE + "moe/experts/experts.forward_all_experts/experts._mlp/eci,eih->ech/dot_general:"
    assert tp.label({"tf_op": moe}, TABLE) == "experts:experts._mlp/eci,eih->ech"
    assert tp.label({"tf_op": "jit(f)/jvp(attend)/flash_fwd/pallas_call:"}, TABLE) == \
        "named_other:attend/flash_fwd"
    assert tp.label({"tf_op": "jit(f)/add:"}, TABLE) == "unnamed" == tp.label({}, TABLE)
    # merged ops carry several stacks: the first names them
    both = DECODE + "attention/le:;" + DECODE + "attention/broadcast_in_dim:"
    assert tp.scope(both, TABLE)[-2:] == ["block", "attention"]


def test_the_table_names_no_part_twice_and_every_row_compiles():
    raw = json.loads(tp.TABLE.read_text())
    parts = [row[0] for row in raw["parts"]]
    assert len(parts) == len(set(parts)) and {"named_other", "unnamed"}.isdisjoint(parts)
    assert all(row[1] in ("tf_op", "hlo_category") for row in raw["parts"] + raw["passes"])
    assert set(tp.ATTENTION + tp.FFN + ("sampler",)) <= set(parts)


# ------------------------------------------------------- a made-up two chips

def made_up_trace() -> bytes:
    names = b"".join(stat_metadata(i, n) for i, n in STATS.items())

    def op(mid, text, tf_op, category, flops=0, nbytes=0, program=99):
        stats = [stat(2, str=category), stat(3, uint64=flops), stat(4, uint64=nbytes),
                 stat(5, uint64=program)]
        if tf_op:
            stats.append(stat(1, str=tf_op))
        return event_metadata(mid, text, *stats)

    meta = (
        op(1, "%while.1 = () while(() %t)", "jit(step_fn)/while:", "while", flops=10**9),
        op(2, "%fusion.5 = bf16[8] fusion(bf16[8] %a)", TRAIN + "closed_call/layers/layers/"
           "checkpoint/rematted_computation/block/mlp/up/dot_general:", "convolution fusion",
           flops=4000, nbytes=100),
        op(3, '%flash_fwd.1 = bf16[8] custom-call(bf16[8] %q), custom_call_target="tpu_custom_call"',
           "jit(step_fn)/jvp(Net.loss)/Net._hidden/"
           "layers/block/attention/flash_fwd/pallas_call:", "custom-call"),
        op(4, "%all-reduce.2 = f32[8] all-reduce(f32[8] %g)", TRAIN + "closed_call/layers/layers/"
           "checkpoint/block/mlp/down/psum:", "all-reduce", nbytes=32),
        op(5, "%convert.9 = f32[8] convert(bf16[8] %fusion.5)", "", "non-fusion elementwise", nbytes=8),
        op(6, "%fusion.5 = f32[2] fusion(f32[2] %a)", "jit(other)/add:", "loop fusion", program=7),
        event_metadata(7, "jit_step_fn(99)"), event_metadata(8, "jit_other(7)"))
    us = 1_000_000                       # picoseconds
    chip0 = plane("/device:TPU:0", names, *meta,
                  line("XLA Ops", 0, (1, 1 * us, 8 * us),            # the while spans its children
                       (2, 1 * us, 2 * us), (3, 3 * us, 1 * us), (4, 4 * us, 2 * us),
                       (5, 6 * us, 1 * us), (2, 9 * us, 2 * us),     # half of it after the window
                       (6, 20 * us, 5 * us)),                        # all of it after the window
                  line("XLA Modules", 0, (7, 1 * us, 10 * us), (8, 20 * us, 5 * us)))
    chip1 = plane("/device:TPU:1", names, *meta,
                  line("XLA Ops", 0, (2, 1 * us, 4 * us), (4, 5 * us, 2 * us)),
                  line("XLA Modules", 0, (7, 1 * us, 6 * us)))
    host = plane("/host:CPU", event_metadata(1, "bm:traced_window"),
                 line("python3", 0, (1, 0, 10 * us)))
    return chip0 + chip1 + host


def test_reduction_by_name_of_two_made_up_chips():
    got = tp.reduce_by_name(made_up_trace())
    us = 1e-6
    # seconds are means over the two chips; chip 0's second fusion.5 is cut in half by the window
    assert got["part_s"] == {"jit_step_fn": pytest.approx({
        "ffn": (2 + 1 + 4 + 1) / 2 * us, "attention": 0.5 * us, "collective": 2 * us})}
    assert got["pass_s"]["jit_step_fn"] == pytest.approx({
        "recompute": 4 * us, "forward": 0.5 * us, "backward": 2 * us})
    assert got["category_hlo_s"] == pytest.approx({
        "convolution fusion": 3.5 * us, "custom-call": 0.5 * us, "all-reduce": 2 * us,
        "non-fusion elementwise": 0.5 * us})
    assert got["flops"] == pytest.approx({"jit_step_fn": 4000 * (1 + 0.5 + 1) / 2})   # not the while's
    assert got["bytes_accessed"]["jit_step_fn"] == pytest.approx((100 * 2.5 + 32 * 2 + 8) / 2)
    assert got["custom_call_s"] == pytest.approx({"flash_fwd": 0.5 * us})
    assert got["custom_call_n"] == pytest.approx({"flash_fwd": 0.5})
    # the nameless convert reads fusion.5 and is counted, and labelled, as what that is
    assert got["unnamed_s"] == 0 and got["inherited_s"] == pytest.approx(0.5 * us)
    assert got["op_labels"] == {"fusion.5": "ffn:mlp/up", "all-reduce.2": "collective:mlp/down",
                                "flash_fwd.1": "attention:attention/flash_fwd", "convert.9": "~ffn:mlp/up"}
    assert tp.reduce_by_name(plane("/host:CPU")) is None


@pytest.mark.parametrize("trace", ["small_trace_1chip.xplane.pb", "small_trace_4chip.xplane.pb"])
def test_agrees_with_trace_reduce_on_the_recorded_traces(trace):
    """Two readers of one file: the time by part, by pass and by hlo_category
    each sum to ``trace_reduce``'s time by category (nanosecond rounding
    apart), and its keys are what they were (this file adds none to it)."""
    old = trace_reduce.reduce_file(str(DATA / trace))
    assert set(old) == {"devices", "window_s", "busy_s", "busy_s_per_device", "category_s", "module_s",
                        "module_calls", "device_ops", "idle_gaps", "longest_gap_s"}
    new = tp.reduce_file(str(DATA / trace))
    total = sum(old["category_s"].values())
    for key in ("part_s", "pass_s"):
        assert sum(s for d in new[key].values() for s in d.values()) == pytest.approx(total, rel=1e-4)
        assert set(new[key]) <= set(old["module_s"])
    assert sum(new["category_hlo_s"].values()) == pytest.approx(total, rel=1e-4)
    assert set(new["op_labels"]) == {name for name, _ in old["device_ops"]}
    # the matmuls' FLOPs over their seconds, both inside the window (which cuts the four-chip
    # trace's first execution): 190 TFLOP/s, under the table's 197
    assert 185e12 < new["flops"]["jit_bm_matmuls"] / new["category_hlo_s"]["convolution fusion"] < 197e12
    if "1chip" in trace:                   # three whole executions of four matmuls
        assert new["flops"]["jit_bm_matmuls"] == pytest.approx(12 * 17188257792, rel=1e-6)
    if "4chip" in trace:
        assert new["part_s"]["jit__lambda"]["collective"] == pytest.approx(
            old["category_s"]["collective"], rel=1e-4)
        assert new["category_hlo_s"]["all-reduce"] == pytest.approx(old["category_s"]["collective"], rel=1e-4)
    else:                                  # jit(bm_matmuls)/dot_general: a function, no module
        assert new["unnamed_s"] == pytest.approx(old["busy_s"], rel=1e-3) and new["inherited_s"] == 0
        assert new["category_hlo_s"]["convolution fusion"] == pytest.approx(old["busy_s"], rel=1e-3)


def test_the_named_trace_recorded_on_the_v5e():
    """``benchmark/record_trace_named.py`` on one chip: a two-layer flax model
    (``attention`` and ``mlp`` under ``nn.scan``), a ``loss`` scope, a
    ``value_and_grad`` and a ``pallas_call`` named ``bm_double``. The device's
    clock runs 0.6 ms ahead of the host's here, so the window holds the second
    of the two executions only."""
    import gzip

    got = tp.reduce_by_name(gzip.decompress((DATA / "small_trace_named.xplane.pb.gz").read_bytes()))
    (module, parts), = got["part_s"].items()
    assert module == "jit_bm_named_step"
    total = sum(parts.values())
    assert {"ffn", "attn_proj", "attention", "loss", "scan_carry", "compiler_copy"} <= set(parts)
    assert parts["ffn"] > parts["attn_proj"] > parts["attention"] > parts["loss"] > 0
    assert got["unnamed_s"] < 0.1 * total                  # a convert the compiler made
    passes = got["pass_s"][module]
    assert passes["backward"] > passes["forward"] > 0 and "recompute" not in passes
    assert set(got["custom_call_n"]) == {"bm_double"}      # not the buffer allocations
    assert got["custom_call_n"]["bm_double"] == pytest.approx(1.0)
    assert got["category_hlo_s"]["convolution fusion"] > 0.5 * total
    # XLA's count for the executed ops: 2 layers x (qkv, scores, values, o_proj, up, down) forward
    # and twice that backward, at 2 x 256 tokens of width 512: 20.9 GFLOP of matmuls
    assert 19e9 < got["flops"][module] < 23e9
    labels = set(got["op_labels"].values())
    assert {"ffn:mlp/up", "ffn:mlp/down", "attn_proj:attention/qkv", "compiler_copy"} <= labels


# ---------------------------------------------------------------- the shares

ROOT = Path(harness.ROOT)
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


def decode_record():
    return {
        "rows": [{"due": 0.0, "submitted": 0.0, "stamps": [0.2, 1.0, 1.0, 2.0], "failed": False,
                  "prompt_tokens": 1000, "want": 4}],
        "config": config("mistral-7b-v0.3"), "peaks": PEAKS, "chips": 1, "traced": [0.0, 4.0],
        "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 4096},
        "device_trace": {
            "devices": 1, "window_s": 4.0, "busy_s": 3.0,
            "module_s": {"jit_fused_fn": 2.4, "jit_insert_fn": 0.5},
            "module_calls": {"jit_fused_fn": 2.0, "jit_insert_fn": 2.0},
            "part_s": {"jit_fused_fn": {"attention": 0.6, "kv_gather": 0.5, "attend": 0.1, "attn_proj": 0.2,
                                        "ffn": 0.48, "router": 0.06, "experts": 0.06, "sampler": 0.024,
                                        "unnamed": 0.3},
                       "jit_insert_fn": {"experts": 0.45, "attention": 0.05}},
            "bytes_accessed": {"jit_fused_fn": 3.0e10},
        },
    }


DECODE_SHARES = {
    "decode.attention_share": 100 * 1.2 / 2.4,         # attention + kv_gather + attend, not attn_proj
    "decode.ffn_share": 100 * 0.6 / 2.4,
    "decode.sampler_share": 1.0,
    "prefill.experts_share": 90.0,
}


@pytest.mark.parametrize("name", sorted(DECODE_SHARES))
def test_serving_share(name):
    assert tp.SHARES[name](decode_record()) == pytest.approx(DECODE_SHARES[name])


def test_bytes_over_needed_divides_by_what_the_roofline_share_needs():
    rec = decode_record()
    step_s = 2.4 / 3                   # the row's stamps: blocks at 1.0 (two tokens) and 2.0 (one)
    assert harness.read_layer_metric("decode.step_ms", rec) == pytest.approx(step_s * 1e3)
    share = harness.read_layer_metric("decode.roofline_share", rec)
    need = share / 100 * PEAKS["hbm_bytes_per_s"] * step_s
    assert tp.SHARES["decode.bytes_over_needed"](rec) == pytest.approx(3.0e10 / 3 / need)
    assert 0.3 < tp.SHARES["decode.bytes_over_needed"](rec) < 3    # ~14 GB needed a live step, 10 GB moved


def train_record():
    return {"config": config("pythia-6.9b"), "mix": {"seq_len": 2048}, "peaks": PEAKS, "chips": 4,
            "tokens_per_step": 16384,
            "device_trace": {"devices": 4, "window_s": 3.0, "busy_s": 2.5,
                             "module_s": {"jit_step_fn": 2.5},
                             "category_hlo_s": {"convolution fusion": 1.5, "convolution": 0.25,
                                                "loop fusion": 0.5, "custom-call": 0.25},
                             "flops": {"jit_step_fn": 2.0e14},
                             "custom_call_n": {"flash_fwd": 16.0, "flash_bwd_dkv": 8.0, "flash_bwd_dq": 8.0}}}


def test_training_shares():
    from benchmark import opcount

    rec = train_record()
    assert tp.SHARES["train_step.matmul_share"](rec) == pytest.approx(100 * 1.75 / 2.5)
    # one flash forward over a chip's share of a step's sequences: 8 x 2048 tokens / 4 chips
    fwd = opcount.attention_flops(rec["config"], 2048) * 16384 / 2048 / 4
    done = 2.0e14 + fwd * (16 * 1.0 + 8 * 2.0 + 8 * 1.5)
    assert tp.SHARES["train_step.hw_flops_share"](rec) == pytest.approx(100 * done / 2.5 / 197e12)
    rec["device_trace"]["custom_call_n"] = {}
    assert tp.SHARES["train_step.hw_flops_share"](rec) == pytest.approx(100 * 2.0e14 / 2.5 / 197e12)


@pytest.mark.parametrize("name", sorted(tp.SHARES))
def test_a_share_with_nothing_to_read_is_none(name):
    """A record from a program or a reduction that lacks the new keys (the
    parent's) gives None, and does not raise."""
    old_only = {"device_trace": {"devices": 1, "window_s": 4.0, "busy_s": 3.0,
                                 "category_s": {"other": 3.0},
                                 "module_s": {"jit_fused_fn": 2.4, "jit_insert_fn": 0.5, "jit_step_fn": 1.0},
                                 "module_calls": {"jit_fused_fn": 5.0}},
                "rows": [], "engine": {"block_steps": 8}, "traced": [0.0, 4.0], "peaks": PEAKS,
                "config": config("mistral-7b-v0.3"), "chips": 1}
    assert tp.SHARES[name](old_only) is None
    assert tp.SHARES[name]({}) is None
