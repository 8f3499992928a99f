"""The Mamba-2 / attention hybrid's own benchmark code, on the CPU:
``opcount_hybrid`` against the hand arithmetic at the published sizes, the four
readers on hand-made records (silent on every other configuration's and on a
program without what they read), the mix's blocks, and what the cell promises.
Every assertion names the cells and metrics it is about: none counts the cells
or lists them all (ROADMAP Rule 7)."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import opcount_hybrid as oc
from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
CONFIG, CELL, MIX = "granite-4.0-h-micro", "granite-4.0-h-micro.toolcalls", "toolcalls-closed"
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] != CONFIG]
NEW_METRICS = ["ssm.decode_step_ms", "ssm.decode_step_mfu_share", "ssm.insert_mfu_share",
               "ssm.scan_real_token_share"]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


CFG = config(CONFIG)


# ------------------------------------------------------------------- the count

@pytest.mark.parametrize("count,want", [
    (oc.mamba_params, 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048),
    (oc.mlp_params, 50_331_648), (oc.attention_params, 10_485_760),
    (oc.head_params, 205_520_896), (oc.total_params, 3_191_396_096),
    (oc.state_elements, 524_288), (oc.kv_bytes_per_token, 8_192),
    (oc.state_bytes_per_slot, 36 * (2_097_152 + 3 * 4352 * 2)),
    (oc.kinds, (36, 4)), (oc.d_inner, 4096), (oc.conv_dim, 4352),
], ids=lambda x: getattr(x, "__name__", None))
def test_count_at_the_published_sizes_is_the_hand_arithmetic(count, want):
    assert count(CFG) == want


def test_the_layers_add_up_to_the_whole_model():
    """A Mamba layer 76 182 976, an attention layer 60 821 504; 36 + 4 of
    them, the final norm and the tied embedding: 6.38 GB in bf16."""
    assert oc.mamba_params(CFG) == 25_847_232
    assert oc.layer_params(CFG, "mamba") == 76_182_976
    assert oc.layer_params(CFG, "attention") == 60_821_504
    assert 36 * 76_182_976 + 4 * 60_821_504 == 2_985_873_152
    assert oc.total_params(CFG) == 2_985_873_152 + 2048 + 205_520_896
    assert oc.total_params(CFG) * 2 / 1e9 == pytest.approx(6.38, abs=0.005)
    assert oc.state_bytes_per_slot(CFG) / 1e6 == pytest.approx(76.4, abs=0.05)


def test_a_full_step_needs_8_9_gb_and_10_8_ms():
    """16 live rows near 400 cached tokens: every weight once, 16 slots' state
    read and written, 4 layers' K/V: memory-bound by a factor of twenty."""
    need = oc.decode_step_bytes(CFG, 16, 16 * 400)
    by_hand = 3_191_396_096 * 2 + 16 * 2 * 36 * (2_097_152 + 26_112) + 6400 * 8192
    assert need == by_hand and need / 1e9 == pytest.approx(8.9, abs=0.03)
    assert need / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(10.8, abs=0.06)
    flops = oc.decode_step_flops(CFG, 16, 16 * 400)
    assert flops == 16 * (2 * 3_191_396_096 + 36 * 5 * 524_288) + 4 * 4 * 32 * 64 * 6400
    assert oc.decode_step_roofline_s(CFG, 16, 6400, PEAKS) == need / PEAKS["hbm_bytes_per_s"]
    assert flops / PEAKS["bf16_flops_per_s"] < 0.06 * need / PEAKS["hbm_bytes_per_s"]
    # the live rows count (their state), the weights do not; one row: 6.5 GB
    assert (oc.decode_step_bytes(CFG, 16, 0) - oc.decode_step_bytes(CFG, 15, 0)
            == 2 * oc.state_bytes_per_slot(CFG))
    assert oc.decode_step_bytes(CFG, 1, 400) / 1e9 == pytest.approx(6.54, abs=0.01)
    # the state is 28 % of a full step's bytes, the Mamba mixers' weights 21 %
    assert 16 * 2 * oc.state_bytes_per_slot(CFG) / need == pytest.approx(0.275, abs=0.005)
    assert 36 * oc.mamba_params(CFG) * 2 / need == pytest.approx(0.21, abs=0.005)


def test_an_insert_needs_3_1_tflop_a_row_at_the_bucket():
    one = oc.insert_flops(CFG, [512])
    layers = 2_985_873_152 - 0
    by_hand = (512 * (2 * layers + 36 * 5 * 524_288) + 4 * 4 * 32 * 64 * 512 * 513 / 2
               + 2 * 205_520_896)
    assert one == pytest.approx(by_hand) and one / 1e12 == pytest.approx(3.1, abs=0.02)
    assert oc.insert_flops(CFG, [200, 300]) == pytest.approx(
        oc.insert_flops(CFG, [200]) + oc.insert_flops(CFG, [300]))
    # the recurrence, one token at a time, is 1.5 % of an insert's operations
    assert 512 * 36 * oc.recurrence_flops_per_token(CFG) / one == pytest.approx(0.0155, abs=0.001)


# ----------------------------------------------------------------- the readers

def row(first, blocks, prompt):
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


def record(cfg=None, stats=True):
    """Two blocks in the traced stretch (10 s, 20 s]: A runs 8 + 3 live steps,
    B 5 (11 live steps in 0.132 s of the fused decode's device time); A's and
    B's inserts lie before it, C's (300 tokens) inside, 0.05 s of insert."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=400)
    b = row(9.5, [(11.0, 5)], prompt=250)
    c = row(12.5, [], prompt=300)
    engine_stats = {"decode_blocks": 3, "ssm_scan_tokens": 950, "ssm_scan_positions": 1536}
    return {"rows": [a, b, c], "config": cfg or CFG, "peaks": PEAKS, "chips": 1,
            "traced": [10.0, 20.0], "engine_stats": engine_stats if stats else {},
            "engine": {"block_steps": 8, "max_batch": 16, "max_seq_len": 1024},
            "device_trace": {"devices": 1, "window_s": 10.0, "busy_s": 0.182,
                             "module_s": {"jit_fused_fn": 0.132, "jit_insert_fn": 0.05},
                             "module_calls": {"jit_fused_fn": 2.0, "jit_insert_fn": 1.0}}}


def test_decode_step_ms_and_its_share_of_the_peak_by_hand():
    """11 live steps of 12 ms; rows (8 + 5 + 3) / 11; A's context 409..416
    then 417..419, B's 251..255."""
    rec = record()
    assert harness.read_layer_metric("ssm.decode_step_ms", rec) == pytest.approx(12.0)
    context = (sum(range(409, 420)) + sum(range(251, 256))) / 11
    least = oc.decode_step_roofline_s(CFG, 16 / 11, context, PEAKS)
    share = harness.read_layer_metric("ssm.decode_step_mfu_share", rec)
    assert share == pytest.approx(100 * least / 0.012) and 60 < share < 75
    # a step that takes the roofline's time reads 100 %, and no step can take less
    rec["device_trace"]["module_s"]["jit_fused_fn"] = 11 * least
    assert harness.read_layer_metric("ssm.decode_step_mfu_share", rec) == pytest.approx(100.0)


def test_insert_mfu_share_by_hand():
    """One insert in the stretch, of 300 real tokens, in 50 ms."""
    share = harness.read_layer_metric("ssm.insert_mfu_share", record())
    assert share == pytest.approx(100 * oc.insert_flops(CFG, [300]) / 197e12 / 0.05)
    assert 15 < share < 25


def test_scan_real_token_share_by_hand():
    assert harness.read_layer_metric("ssm.scan_real_token_share", record()) == \
        pytest.approx(100 * 950 / 1536)


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("other", OTHERS)
def test_new_reader_is_silent_on_another_configurations_record(metric, other):
    """Another configuration's program has no such layers and no such
    counters (its ``engine_stats`` read 0 under these names)."""
    rec = record(cfg=config(other))
    rec["engine_stats"].update(ssm_scan_tokens=0, ssm_scan_positions=0)
    assert harness.read_layer_metric(metric, rec) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("lacks", ["every counter", "ssm_scan_positions", "the traced stretch",
                                   "the insert's module", "the decode's module", "an untraced run"])
def test_new_reader_is_silent_on_a_program_without_what_it_reads(metric, lacks):
    """The parent's program has neither the counters nor the configuration, an
    untraced run no device trace: the reader returns None and never raises."""
    rec = record(stats=lacks != "every counter")
    rec["engine_stats"].pop(lacks, None)
    if lacks == "the traced stretch":
        rec["traced"] = [None, None]
    if lacks == "an untraced run":
        rec["device_trace"], rec["traced"] = None, [None, None]
    if lacks == "the insert's module":
        del rec["device_trace"]["module_s"]["jit_insert_fn"]
    if lacks == "the decode's module":
        del rec["device_trace"]["module_calls"]["jit_fused_fn"]
    reads = {"ssm.decode_step_ms": {"the traced stretch", "the decode's module", "an untraced run"},
             "ssm.decode_step_mfu_share": {"the traced stretch", "the decode's module",
                                           "an untraced run"},
             "ssm.insert_mfu_share": {"the traced stretch", "the insert's module",
                                      "an untraced run"},
             "ssm.scan_real_token_share": {"every counter", "ssm_scan_positions"}}
    got = harness.read_layer_metric(metric, rec)
    assert (got is None) == (lacks in reads[metric])


# --------------------------------------------------------------------- the mix

def test_every_seed_offers_the_same_work_in_blocks_of_64():
    """The closed loop's stream is cut into blocks of 64 requests; each block
    holds the same multiset of prompt and of answer lengths whatever the seed:
    the 64 quantiles of uniform 192-448 and of uniform 32-160, ISSUE 44's.
    What the seed still draws is each block's ORDER (the second assertion of
    the loop), and that is what the cell's spread follows (PERF.md section 6)."""
    mix = traffic.load_mix(MIX)
    offered = {}
    for seed in (1, 2147490101):
        stream = traffic.closed_loop(mix, 100_352, seed)
        reqs = [next(stream) for _ in range(192)]
        offered[seed] = [(sorted(r.prompt.size for r in reqs[i: i + 64]),
                          sorted(r.max_new_tokens for r in reqs[i: i + 64]))
                         for i in range(0, 192, 64)]
        assert all(block == offered[seed][0] for block in offered[seed])
        assert [r.prompt.size for r in reqs[:64]] != sorted(r.prompt.size for r in reqs[:64])
    assert offered[1] == offered[2147490101]
    prompts, answers = offered[1][0]
    assert (prompts[0], prompts[-1], answers[0], answers[-1]) == (194, 446, 33, 159)
    assert sum(prompts) == 64 * 320 and sum(answers) == 64 * 96
    # one bucket, and room for the longest answer in the table
    assert traffic.length_range(mix["prompt_tokens"]) == (192, 448)
    assert 128 < 192 and 448 + 160 < mix["max_seq_len"] == 1024
    assert mix["loop"] == "closed" and mix["clients"] == 32 and mix["limits"] is None
    assert mix["shared_prefix"] is None and (mix["drain_s"], mix["trace_s"]) == (30, 6)


# -------------------------------------------------------------------- the cell

PHASE_SEVEN = ["engine.admit_ms_per_block", "engine.observe_ms_per_block",
               "engine.launch_ms_per_block", "engine.harvest_ms_per_block",
               "engine.insert_stall_ms_per_block", "engine.queue_wait_ms_mean",
               "cache.host_ms_per_insert"]


def test_the_new_metrics_list_the_new_cell_only_and_stand_at_the_end():
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tokens_per_s"
        assert by_name[name]["layer"] == "model programs"
    # appended in this order after everything the benchmark had (ROADMAP Rule 8): the driver
    # reads an entry put in the middle as a change to the one whose place it took
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 4] == NEW_METRICS and at > names.index(PHASE_SEVEN[-1])
    assert [by_name[n]["unit"] for n in NEW_METRICS] == ["ms", "%", "%", "%"]
    assert [by_name[n]["source"] for n in NEW_METRICS] == ["device_trace"] * 3 + ["program_counter"]
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert {"setup.compile_s", "setup.programs", "prefill.ms_per_call",
            "kernels.mosaic_time_share", "device.busy_share", *NEW_METRICS} == listed
    # it joins no list that a snapshot pins, and nothing that moves the time per token
    for m in BENCH["per_layer"]:
        if m["moves"] == "tpot_ms_p50" or m["name"] == "cache.host_ms_per_insert":
            assert CELL not in m.get("workloads", [])


def test_the_seven_phase_metrics_stand_as_pr_39_left_them():
    """What ``test_bm_phase_spans.py``'s snapshot guards besides the place of
    the seven in the list (``tests/conftest.py`` says why that one assertion
    cannot hold any more): the seven stand together, in PR 39's order, right
    before this PR's four, with PR 39's units, sources, layers and cells."""
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    at = names.index(PHASE_SEVEN[0])
    assert names[at:at + 7] == PHASE_SEVEN and names[at + 7:at + 11] == NEW_METRICS
    tpot = next(m for m in BENCH["end_to_end"] if m["name"] == "tpot_ms_p50")["workloads"]
    open_loop = [w for w in tpot if w != "deepseek-v2.longctx"]          # PR 39's CHANGES line
    for name in PHASE_SEVEN:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"]) == ("ms", "lower", "program_span")
        if name.startswith("engine."):
            assert (m["layer"], m["moves"], m["workloads"]) == ("scheduler", "tpot_ms_p50", open_loop)
        else:
            assert (m["layer"], m["moves"], m["workloads"]) == ("cache", "tokens_per_s",
                                                                ["mixtral-8x7b.score"])


def test_the_cell_is_judged_on_tokens_per_second_and_setup():
    e2e = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    by_name = {m["name"]: m for m in BENCH["end_to_end"]}
    assert by_name["tokens_per_s"]["workloads"][-1] == CELL          # appended, not inserted
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert BENCH["workloads"][-1] == cell and BENCH["configs"][-1]["name"] == CONFIG


def test_the_configuration_is_the_published_one_whole():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and CFG["reduced"] == {}
    pub = CFG["published"]
    assert all(CFG[k] == v for k, v in pub.items())
    assert (pub["num_hidden_layers"], pub["vocab_size"], pub["hidden_size"]) == (40, 100_352, 2048)
    assert [i for i, t in enumerate(pub["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert (pub["num_local_experts"], pub["num_experts_per_tok"]) == (0, 0)
    assert CFG["head_dim"] == pub["hidden_size"] // pub["num_attention_heads"] == 64
    assert "head_dim" not in pub and "head_dim" in CFG["assumed"]
    assert CFG["serving"] == {"max_batch": 16, "page_size": 16, "prefix_cache": False}
    assert CFG["ssm_state_dtype"] == "float32" and "ssm_state_dtype" in CFG["assumed"]
    # the rehearsal keeps the period
    small = CFG["rehearsal"]
    assert small["layer_types"] == pub["layer_types"][:10] and small["num_hidden_layers"] == 10
    assert small["mamba_n_heads"] * small["mamba_d_head"] == pub["mamba_expand"] * small["hidden_size"]


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark" / "reference" / "granite_hybrid.py").read_text()
    assert "neuronx_distributed_tpu" not in text.split('"""', 2)[2]
    assert "lax.scan" in text and "ssd" not in text.split('"""', 2)[2].lower()


def test_the_tolerances_lie_between_the_readings_and_the_control():
    """Each limit above every reading of the change and below the float8
    control's, which therefore fails by both (the readings: the file's own
    reasons and PERF.md section 6)."""
    ref = CFG["reference"]
    for limit, why in ((ref["tolerance"], ref["tolerance_why"]),
                       (ref["tolerance_any"], ref["tolerance_any_why"])):
        assert 0 < limit < 0.1 and "float8" in why and len(why) > 200
    assert ref["tolerance"] < ref["tolerance_any"]
    assert np.isfinite([ref["tolerance"], ref["tolerance_any"]]).all()
