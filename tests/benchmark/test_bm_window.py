"""The window / full attention stack's own benchmark code, on the CPU:
``opcount_window`` against the hand arithmetic at the published sizes, the
three readers on hand-made records (silent on every other configuration's and
on a program without what they read), what the configuration file states and
what the cell promises, and the cell's rehearsal end to end. Every assertion
names the cells and metrics it is about: none counts the cells or lists a
place that a later cell would move (ROADMAP Rule 7). The three snapshots this
PR's entries move (``tests/conftest.py``) are asserted here for today's cells."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import opcount_window as oc
from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
CONFIG, CELL, MIX = "laguna-s-2.1", "laguna-s-2.1.longctx", "longctx-window"
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] != CONFIG]
NEW_METRICS = ["swa.decode_step_mfu_share", "swa.insert_mfu_share", "swa.window_read_over_needed"]
FULL, SLIDING = "full_attention", "sliding_attention"


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


CFG = config(CONFIG)


# ------------------------------------------------------------------- the count

@pytest.mark.parametrize("count,want", [
    (lambda c: oc.attention_params(c, FULL), 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48),
    (lambda c: oc.attention_params(c, SLIDING), 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72),
    (oc.dense_mlp_params, 113_246_208), (oc.expert_params, 9_437_184),
    (oc.shared_params, 9_437_184), (oc.router_params, 786_432), (oc.head_params, 308_281_344),
    (oc.kv_bytes_per_token, 4096), (oc.expert_layers, 8),
    (lambda c: (oc.layers_of(c, FULL), oc.layers_of(c, SLIDING)), (3, 6)),
    (lambda c: (oc.heads(c, FULL), oc.heads(c, SLIDING)), (48, 72)),
], ids=["full_attention", "sliding_attention", "dense_mlp", "expert", "shared", "router", "head",
        "kv_bytes", "expert_layers", "kinds", "heads"])
def test_count_at_the_published_sizes_is_the_hand_arithmetic(count, want):
    assert count(CFG) == want


def test_the_layers_add_up_to_the_issues_figures():
    """ISSUE 49's reckoning: attention 44.2 M full and 63.1 M sliding, layer 0
    157.4 M, a sliding expert layer as held 375.3 M, a full one 356.4 M,
    embedding and head 616.6 M: 3.74 B parameters = 7.48 GB in bf16."""
    million = 1e6
    assert oc.attention_params(CFG, FULL) / million == pytest.approx(44.2, abs=0.05)
    assert oc.attention_params(CFG, SLIDING) / million == pytest.approx(63.1, abs=0.05)
    assert oc.layer_params(CFG, FULL, True, 0) / million == pytest.approx(157.4, abs=0.05)
    assert oc.layer_params(CFG, SLIDING, False, 32) / million == pytest.approx(375.3, abs=0.1)
    assert oc.layer_params(CFG, FULL, False, 32) / million == pytest.approx(356.4, abs=0.05)
    assert 2 * oc.head_params(CFG) / million == pytest.approx(616.6, abs=0.05)
    assert oc.layer_kinds(CFG) == [(FULL, True)] + [(SLIDING, False)] * 3 + [(FULL, False)] + \
        [(SLIDING, False)] * 3 + [(FULL, False)]
    total = (oc.layer_params(CFG, FULL, True, 0) + 6 * oc.layer_params(CFG, SLIDING, False, 32)
             + 2 * oc.layer_params(CFG, FULL, False, 32) + 3072 + 2 * oc.head_params(CFG))
    assert oc.total_params(CFG) == total
    assert total / 1e9 == pytest.approx(3.74, abs=0.005) and 2 * total / 1e9 == pytest.approx(7.48, abs=0.01)
    # a whole expert layer's 256 experts are 4.83 GB: a chip cannot hold three
    assert 256 * oc.expert_params(CFG) * 2 / 1e9 == pytest.approx(4.83, abs=0.005)
    # the cache: pages of 3 full layers for 8 rows of 8192, rings of 528 for 6 layers x 8 slots
    assert 3 * 8 * 8192 * 4096 / 1e9 == pytest.approx(0.81, abs=0.006)
    assert 6 * 8 * 528 * 4096 / 1e9 == pytest.approx(0.10, abs=0.005)
    # under one block table each of the 6 window layers would hold 268 MB where 17 is needed
    assert 8 * 8192 * 4096 / 1e6 == pytest.approx(268.4, abs=0.1) and 8 * 528 * 4096 / 1e6 == pytest.approx(17.3, abs=0.1)


def test_a_three_row_step_needs_2_7_gb_and_3_3_ms():
    """3 live rows near 3000 cached tokens, 3.5 experts read an expert layer:
    the attention weights 1.05 GB, the experts read 0.53, the head 0.62, the
    dense MLP and the shared experts 0.38, the full layers' cache 0.11, the
    rings' windows 0.04."""
    need = oc.decode_step_bytes(CFG, 3, 3 * 3000, 3 * 512, 3.5)
    attention = 3 * oc.attention_params(CFG, FULL) + 6 * oc.attention_params(CFG, SLIDING)
    by_hand = 2 * (attention + 9 * 2 * 3072 + oc.dense_mlp_params(CFG)
                   + 8 * (oc.shared_params(CFG) + oc.router_params(CFG) + 3.5 * oc.expert_params(CFG))
                   + 3072 + oc.head_params(CFG) + 3 * 3072) + (3 * 9000 + 6 * 1536) * 4096
    assert need == pytest.approx(by_hand) and need / 1e9 == pytest.approx(2.71, abs=0.01)
    assert attention * 2 / 1e9 == pytest.approx(1.02, abs=0.01)
    assert need / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(3.3, abs=0.02)
    flops = oc.decode_step_flops(CFG, 3, 9000, 1536, 30)
    assert flops / PEAKS["bf16_flops_per_s"] < 0.05 * need / PEAKS["hbm_bytes_per_s"]
    assert oc.decode_step_roofline_s(CFG, 3, 9000, 1536, 3.5, 30, PEAKS) == \
        pytest.approx(need / PEAKS["hbm_bytes_per_s"])
    # a window layer reads its window, not the row: 9000 cached tokens in 6
    # window layers would be 221 MB more
    whole = oc.decode_step_bytes(CFG, 3, 9000, 9000, 3.5)
    assert whole - need == 6 * (9000 - 1536) * 4096
    # every held expert read is 0.6 GB more than 3.5 of them
    assert oc.decode_step_bytes(CFG, 3, 9000, 1536, 32) - need == \
        pytest.approx(8 * 28.5 * oc.expert_params(CFG) * 2)


def test_an_insert_of_2800_tokens_needs_5_tflop():
    one = oc.insert_flops(CFG, [2800], 10.0)
    per_token = (3 * oc.attention_params(CFG, FULL) + 6 * oc.attention_params(CFG, SLIDING)
                 + 9 * 2 * 3072 + oc.dense_mlp_params(CFG)
                 + 8 * (oc.shared_params(CFG) + oc.router_params(CFG)) + 10 * oc.expert_params(CFG))
    band = 512 * 513 / 2 + (2800 - 512) * 512
    by_hand = (2 * 2800 * per_token + 2 * oc.head_params(CFG)
               + 4 * 128 * (3 * 48 * 2800 * 2801 / 2 + 6 * 72 * band))
    assert one == pytest.approx(by_hand) and one / 1e12 == pytest.approx(5.06, abs=0.02)
    assert per_token / 1e9 == pytest.approx(0.80, abs=0.01)       # the active parameters a token
    assert oc.insert_flops(CFG, [1200, 3000], 10.0) == pytest.approx(
        oc.insert_flops(CFG, [1200], 10.0) + oc.insert_flops(CFG, [3000], 10.0))
    # over the triangle the window layers' attention would be 3 times the band's
    assert oc.band_pairs(2800, None) / oc.band_pairs(2800, 512) == pytest.approx(3.01, abs=0.01)
    assert oc.band_pairs(300, 512) == 300 * 301 / 2 and oc.band_pairs(512, 512) == 512 * 513 / 2


def test_the_windowed_flash_calls_own_count():
    """One window layer's call over 8 prompts of a 4096 bucket: 0.58 TFLOP over
    the band (2.47 over the triangle), 1.34 GB of q, o, k and v."""
    flops = oc.flash_window_flops(CFG, 8, 4096)
    assert flops == 8 * 4 * 128 * 72 * (512 * 513 / 2 + 3584 * 512)
    assert flops / 1e12 == pytest.approx(0.58, abs=0.01)
    assert oc.flash_window_bytes(CFG, 8, 4096) == 8 * 4096 * 128 * 2 * (144 + 16)
    least = max(flops / PEAKS["bf16_flops_per_s"],
                oc.flash_window_bytes(CFG, 8, 4096) / PEAKS["hbm_bytes_per_s"])
    assert least * 1e3 == pytest.approx(2.94, abs=0.02)           # compute-bound


# ----------------------------------------------------------------- the readers

def row(first, blocks, prompt):
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


STATS = {"decode_blocks": 3, "kv_walk_steps": 24, "moe_layer_steps": 192,
         "moe_experts_touched": 480, "moe_assignments": 600, "moe_assignments_routed": 4800,
         "moe_insert_assignments": 46_500, "kv_window_slots_read": 228_096,
         "kv_window_slots_needed": 165_888}


def record(cfg=None, stats=True):
    """Two blocks in the traced stretch (10 s, 20 s]: A runs 8 + 3 live steps,
    B 5 (11 live steps in 0.055 s of the fused decode's device time); A's and
    B's inserts lie before it, C's (1500 tokens) inside, 0.040 s of insert."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=3000)
    b = row(9.5, [(11.0, 5)], prompt=400)
    c = row(12.5, [], prompt=1500)
    return {"rows": [a, b, c], "config": cfg or CFG, "peaks": PEAKS, "chips": 1,
            "traced": [10.0, 20.0], "engine_stats": dict(STATS) if stats else {},
            "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 8192},
            "device_trace": {"devices": 1, "window_s": 10.0, "busy_s": 0.095,
                             "module_s": {"jit_fused_fn": 0.055, "jit_insert_fn": 0.04},
                             "module_calls": {"jit_fused_fn": 2.0, "jit_insert_fn": 1.0}}}


def test_decode_step_share_of_the_peak_by_hand():
    """11 live steps of 5 ms; rows (8 + 5 + 3) / 11; A's context 3009..3016
    then 3017..3019, B's 401..405: B lies inside the window (512) and needs
    what it holds, A needs 512 a step."""
    rec = record()
    context = (sum(range(3009, 3020)) + sum(range(401, 406))) / 11
    window_tokens = (11 * 512 + sum(range(401, 406))) / 11
    least = oc.decode_step_roofline_s(CFG, 16 / 11, context, window_tokens, 480 / 192, 600 / 24,
                                      PEAKS)
    share = harness.read_layer_metric("swa.decode_step_mfu_share", rec)
    assert share == pytest.approx(100 * least / 0.005) and 50 < share < 70
    # a step that takes the roofline's time reads 100 %, and no step can take less
    rec["device_trace"]["module_s"]["jit_fused_fn"] = 11 * least
    assert harness.read_layer_metric("swa.decode_step_mfu_share", rec) == pytest.approx(100.0)


def test_insert_mfu_share_by_hand():
    """One insert in the stretch, of 1500 real tokens, in 40 ms; the window's
    inserts held 46 500 picks over 4900 tokens, 9.49 a token."""
    share = harness.read_layer_metric("swa.insert_mfu_share", record())
    flops = oc.insert_flops(CFG, [1500], 46_500 / 4900)
    assert share == pytest.approx(100 * flops / 197e12 / 0.04) and 30 < share < 40


def test_window_read_over_needed_by_hand():
    """24 steps of 6 window layers: a rung of 3 rows' rings of 528 read where
    2.25 rows' 512 were needed."""
    assert 24 * 6 * 3 * 528 == 228_096
    assert harness.read_layer_metric("swa.window_read_over_needed", record()) == \
        pytest.approx(228_096 / 165_888) == pytest.approx(1.375)


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("other", OTHERS)
def test_new_reader_is_silent_on_another_configurations_record(metric, other):
    """Another configuration's program has no window layers and no such
    counters (its ``engine_stats`` read 0 under these names)."""
    rec = record(cfg=config(other))
    rec["engine_stats"].update(kv_window_slots_read=0, kv_window_slots_needed=0)
    assert harness.read_layer_metric(metric, rec) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("lacks", ["every counter", "kv_window_slots_needed", "moe_layer_steps",
                                   "moe_insert_assignments", "the traced stretch",
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
    untraced = {"the traced stretch", "an untraced run"}
    reads = {"swa.decode_step_mfu_share": untraced | {"every counter", "moe_layer_steps",
                                                      "the decode's module"},
             "swa.insert_mfu_share": untraced | {"every counter", "moe_insert_assignments",
                                                 "the insert's module"},
             "swa.window_read_over_needed": {"every counter", "kv_window_slots_needed"}}
    got = harness.read_layer_metric(metric, rec)
    assert (got is None) == (lacks in reads[metric])


# --------------------------------------------------- the file and what it promises

def test_the_configuration_states_its_cut_its_deployment_and_its_assumptions():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"] == list(CFG["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == CFG["source"]
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"]) == (48, 256)
    assert (CFG["num_hidden_layers"], CFG["num_experts"]) == (9, 32)
    # only the two reduced keys differ from the source; the lists stay 48 long
    assert {k for k, v in pub.items() if CFG[k] != v} == {"num_hidden_layers", "num_experts"}
    assert len(CFG["layer_types"]) == len(CFG["num_attention_heads_per_layer"]) == 48
    assert CFG["layer_types"][:9] == [FULL] + [SLIDING] * 3 + [FULL] + [SLIDING] * 3 + [FULL]
    assert CFG["num_attention_heads_per_layer"][:9] == [48, 72, 72, 72, 48, 72, 72, 72, 48]
    # the router keeps its width and its top-10; the widths are the published ones
    assert (CFG["router_experts"], CFG["num_experts_per_tok"], CFG["num_local_experts"]) == (256, 10, 32)
    assert (CFG["hidden_size"], CFG["head_dim"], CFG["moe_intermediate_size"], CFG["sliding_window"],
            CFG["vocab_size"], CFG["intermediate_size"]) == (3072, 128, 1024, 512, 100_352, 12_288)
    for key in ("scoring_func", "gating", "qk_norm", "hidden_act", "sliding_window",
                "rope_convention", "router_experts", "experts_held_first", "num_local_experts",
                "weights"):
        assert key in CFG["assumed"], key
    # A1-A3 are VALUES the builder hands the program, switchable in the file
    fields = CFG["builder"]["fields"]
    assert (fields["scoring_func"], fields["attention_gate"], fields["qk_norm"]) == \
        ("scoring_func", "gating", "qk_norm")
    assert (CFG["scoring_func"], CFG["gating"], CFG["qk_norm"]) == ("sigmoid", "per-head", False)
    assert "SILENT" in CFG["assumed"]["scoring_func"] and "softmax" in CFG["assumed"]["scoring_func"]
    for said in ("8 chips share each layer", "39 layers", "1.25 picks", "whole (100 352)",
                 "528 tokens a slot"):
        assert said in CFG["deployment"], said
    assert "3.739 B" in CFG["reduced"]["num_hidden_layers"] and "7.48 GB" in CFG["reduced"]["num_hidden_layers"]
    assert CFG["serving"] == {"max_batch": 8, "page_size": 16, "prefix_cache": False}
    small = CFG["rehearsal"]
    assert small["layer_types"] == pub["layer_types"][:5] and small["num_hidden_layers"] == 5
    assert (small["sliding_window"], small["num_key_value_heads"], small["router_experts"],
            small["num_experts"], small["num_experts_per_tok"]) == (8, 2, 16, 8, 3)
    assert small["num_attention_heads_per_layer"] == [4, 6, 6, 6, 4]


def test_the_builder_gives_the_program_the_published_shapes():
    from benchmark.drivers import serving

    mcfg = serving.model_config(CFG, False, max_seq_len=8192, remat_policy=None)
    assert (mcfg.num_layers, mcfg.period, mcfg.num_experts, mcfg.router_experts, mcfg.top_k) == \
        (9, 4, 32, 256, 10)
    assert mcfg.layer_types == (FULL,) + ((SLIDING,) * 3 + (FULL,)) * 2
    assert (mcfg.scoring_func, mcfg.attention_gate, mcfg.qk_norm, mcfg.routed_scaling_factor) == \
        ("sigmoid", "per-head", False, 2.5)
    assert mcfg.of_kind(SLIDING).num_heads == 72 and mcfg.of_kind(FULL).rope_dims == 64
    small = serving.model_config(harness.load_config(
        next(c for c in BENCH["configs"] if c["name"] == CONFIG), rehearse=True), True,
        max_seq_len=1024, remat_policy=None)
    assert (small.num_layers, small.period, small.sliding_window, small.num_experts) == (5, 4, 8, 8)


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark" / "reference" / "laguna.py").read_text()
    body = text.split('"""', 2)[2]
    assert "neuronx_distributed_tpu" not in body and 'default_matmul_precision("highest")' in body
    assert "pallas" not in body and "flash" not in body


def test_the_tolerances_lie_between_the_readings_and_the_control():
    ref = CFG["reference"]
    assert ref["module"] == "laguna"
    for limit, why in ((ref["tolerance"], ref["tolerance_why"]),
                       (ref["tolerance_any"], ref["tolerance_any_why"])):
        assert 0 < limit < 0.1 and "float8" in why and "window" in why and len(why) > 200
    assert ref["tolerance"] < ref["tolerance_any"]


def test_the_mix_is_the_issues_letter_for_letter():
    mix = traffic.load_mix(MIX)
    assert (mix["loop"], mix["arrivals"], mix["shared_prefix"]) == ("open", {"process": "poisson"}, None)
    assert mix["prompt_tokens"] == [{"weight": 1.0, "dist": "lognormal", "median": 2800,
                                     "sigma": 0.4, "min": 1100, "max": 4096}]
    assert mix["answer_tokens"] == [{"weight": 1.0, "dist": "lognormal", "median": 200,
                                     "sigma": 0.5, "min": 64, "max": 512}]
    assert (mix["max_seq_len"], mix["drain_s"], mix["trace_s"]) == (8192, 60, 8)
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert round(mix["rate_per_s"] * BENCH["run_seconds"]) >= 40
    assert traffic.length_range(mix["prompt_tokens"])[0] > CFG["sliding_window"]
    # every seed's window offers the same lengths at the same times (Rule 1)
    a, b = (traffic.open_loop(mix, 1000, seed=s, seconds=51.0) for s in (3, 2_147_483_659))
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in b]
    # rehearsal prompts are longer than the rehearsal's window too
    small = traffic.load_mix(MIX, rehearse=True)
    assert traffic.length_range(small["prompt_tokens"])[0] > CFG["rehearsal"]["sliding_window"]


def test_the_new_entries_stand_at_the_end_and_list_the_new_cell_only():
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 3] == NEW_METRICS and at > names.index("ssm.scan_real_token_share")
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tpot_ms_p50"
    assert [by_name[n]["layer"] for n in NEW_METRICS] == ["model programs"] * 2 + ["cache"]
    assert [by_name[n]["unit"] for n in NEW_METRICS] == ["%", "%", "ratio"]
    assert [by_name[n]["better"] for n in NEW_METRICS] == ["higher", "higher", "lower"]
    assert [by_name[n]["source"] for n in NEW_METRICS] == ["device_trace"] * 2 + ["program_counter"]
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(CELL) > cells.index("granite-4.0-h-micro.toolcalls")
    assert configs.index(CONFIG) > configs.index("granite-4.0-h-micro")
    cell = BENCH["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    mix = traffic.load_mix(MIX)
    assert f"{mix['rate_per_s']:g}/s" in cell["why"] and "attention over its share" in cell["why"]


def test_the_cell_reports_what_the_issue_lists_and_nothing_pinned_elsewhere():
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert listed == {
        "setup.compile_s", "setup.programs", "ttft_ms_p50", "engine.host_ms_per_block",
        "engine.batch_occupancy", "engine.slo_attainment", "decode.step_ms", "device.idle_share",
        "cache.temp_over_pool", "cache.pool_used_peak", "engine.admit_ms_per_block",
        "engine.observe_ms_per_block", "engine.launch_ms_per_block", "engine.harvest_ms_per_block",
        "engine.insert_stall_ms_per_block", "engine.queue_wait_ms_mean", *NEW_METRICS}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in listed - set(NEW_METRICS) - {"setup.compile_s", "setup.programs"}:
        assert by_name[name]["workloads"][-1] == CELL, name          # appended, not inserted
    # its count takes every layer as full / they are pinned to DeepSeek-V2's cell
    for name in ("decode.roofline_share", "decode.latent_roofline_share",
                 "moe.local_assignment_share"):
        assert CELL not in by_name[name]["workloads"], name
    assert by_name["moe.local_assignment_share"]["workloads"] == ["deepseek-v2.longctx"]
    e2e = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CELL)}
    assert e2e == {"tpot_ms_p50", "setup_s"}


# ---------------- what the three snapshots guarded, for the cells that exist

def test_every_open_loop_cell_is_judged_on_the_median_time_per_token_and_no_other():
    """``test_bm_latent.py``'s snapshot (five cells by list) without the list's
    length or its last place: ``end_to_end`` is as PR 34 left it, exactly the
    open-loop cells are judged on the median time per token under 4 %."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    open_loop = sorted(w["name"] for w in BENCH["workloads"]
                       if traffic.load_mix(w["traffic"]).get("loop") == "open")
    tpot = e2e["tpot_ms_p50"]
    assert sorted(tpot["workloads"]) == open_loop
    for cell in ("mixtral-8x7b.chat", "mistral-7b-v0.3.longctx", "olmoe-1b-7b.chat",
                 "mistral-7b-v0.3.chat", "deepseek-v2.longctx", CELL):
        assert cell in open_loop, cell
    assert tpot["workloads"].index("deepseek-v2.longctx") < tpot["workloads"].index(CELL)
    assert (tpot["bound"], tpot["source"], tpot["unit"], tpot["better"]) == \
        (0.04, "host_clock", "ms", "lower")
    assert set(e2e) == {"tpot_ms_p50", "tokens_per_s", "setup_s"}
    assert (e2e["tokens_per_s"]["bound"], e2e["setup_s"]["bound"]) == (0.015, 0.1)
    assert {m["moves"] for m in BENCH["per_layer"]} == set(e2e)


def test_granites_cell_is_judged_on_tokens_per_second_and_setup():
    """``test_bm_hybrid.py``'s snapshot without "the last workload": Granite's
    cell and configuration stand, after DeepSeek-V2's, as PR 44 left them."""
    cell = "granite-4.0-h-micro.toolcalls"
    assert {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", cell)} == \
        {"tokens_per_s", "setup_s"}
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
    assert tokens[-1] == cell and CELL not in tokens
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("granite-4.0-h-micro", "toolcalls-closed", 1)
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(cell) == cells.index("deepseek-v2.longctx") + 1
    assert configs.index("granite-4.0-h-micro") == configs.index("deepseek-v2") + 1


@pytest.mark.parametrize("other", [c for c in OTHERS if c != "deepseek-v2"])
def test_the_held_share_reader_is_silent_where_every_routed_expert_is_held(other):
    """``test_bm_latent.py``'s case for every configuration but the two that
    hold a share; on Laguna's record the reader has something to read (32 of
    256: 12.5 % if the picks fall evenly) though the metric lists DeepSeek-V2's
    cell alone."""
    rec = record(cfg=config(other))
    assert "router_experts" not in rec["config"]
    assert harness.read_layer_metric("moe.local_assignment_share", rec) is None
    assert harness.read_layer_metric("moe.local_assignment_share", record()) == \
        pytest.approx(100 * 600 / 4800)


# ------------------------------------------------------------- the rehearsal

def test_the_cells_rehearsal_runs_end_to_end():
    """``run.py --rehearse``: tiny widths on the host, the same control flow as
    the chip run: build, the reference probe, warm-up of every group, a
    window. The probe's prompts are longer than the rehearsal's window."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    got = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
                          "2147490101", "--seconds", "2", "--trace", "1", "--rehearse"],
                         capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["attempted"] > 0 and line["failed"] == 0
    assert set(NEW_METRICS) <= set(line["would_report"]) and line["metrics"] == {}
    assert line["compared"]["logit_gap_max"]["value"] < 1e-5
    probe = next(json.loads(l) for l in got.stdout.splitlines() if '"phase": "reference"' in l)
    assert min(probe["prompt_lens"]) > CFG["rehearsal"]["sliding_window"] and probe["decode_steps"] == 4
    stats = json.loads((ROOT / "benchmark/out" / f"{CELL}.json").read_text())["record"]["engine_stats"]
    assert stats["kv_window_slots_needed"] > 0
    assert stats["kv_window_slots_read"] >= stats["kv_window_slots_needed"]
