"""The shortcut-connected block's own benchmark code, on the CPU:
``opcount_scmoe`` against the hand arithmetic at the published sizes (ISSUE
52's figures), the three readers on hand-made records (silent on every other
configuration's and on a program without what they read), what the
configuration file states and what the cell promises, and the cell's rehearsal
end to end. Every assertion names the cells and metrics it is about: none
counts the cells or lists a place that a later cell would move (ROADMAP Rule
7). The snapshots this PR's entries move (``tests/conftest.py``, ``_PR_52_MOVED``)
are asserted here, by name, for today's cells."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import opcount_scmoe as oc
from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
CONFIG, CELL, MIX = "longcat-flash-chat", "longcat-flash-chat.longctx", "longctx-scmoe"
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] != CONFIG]
NEW_METRICS = ["scmoe.decode_step_mfu_share", "scmoe.insert_mfu_share", "moe.zero_pick_share"]
APPENDED_TO = ["ttft_ms_p50", "engine.host_ms_per_block", "engine.batch_occupancy",
               "engine.slo_attainment", "decode.step_ms", "cache.temp_over_pool",
               "cache.pool_used_peak", "device.idle_share", "engine.admit_ms_per_block",
               "engine.observe_ms_per_block", "engine.launch_ms_per_block",
               "engine.harvest_ms_per_block", "engine.insert_stall_ms_per_block",
               "engine.queue_wait_ms_mean", "moe.insert_real_row_share",
               "moe.local_assignment_share"]
HOLD_A_SHARE = ["deepseek-v2", "laguna-s-2.1", CONFIG]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


CFG = config(CONFIG)


# ------------------------------------------------------------------- the count

@pytest.mark.parametrize("count,want", [
    (oc.attention_params, 9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648),
    (oc.dense_mlp_params, 3 * 6144 * 12288), (oc.expert_params, 3 * 6144 * 2048),
    (oc.router_width, 768), (oc.router_params, 6144 * 768), (oc.head_params, 6144 * 131072),
    (oc.latent_bytes_per_token_sub_layer, 1152),
    (lambda c: oc.layer_params(c, 0), 2 * 90_570_752 + 2 * 226_492_416 + 4_718_592),
], ids=["attention", "dense_mlp", "expert", "router_width", "router", "head", "latent_bytes",
        "layer_outside_the_experts"])
def test_count_at_the_published_sizes_is_the_hand_arithmetic(count, want):
    assert count(CFG) == want


def test_the_layers_add_up_to_the_issues_figures():
    """ISSUE 52's reckoning: one attention 90.57 M, one dense MLP 226.49 M, the
    router 4.72 M, 638.8 M a layer outside the experts, one expert 37.75 M, a
    layer as held 940.8 M = 1.88 GB, four 7.53 GB, embedding and head 3.22 GB:
    5.37 B parameters = 10.75 GB in bf16, beside 0.30 GB of latent pages."""
    million = 1e6
    assert oc.attention_params(CFG) / million == pytest.approx(90.57, abs=0.005)
    assert oc.dense_mlp_params(CFG) / million == pytest.approx(226.49, abs=0.005)
    assert oc.router_params(CFG) / million == pytest.approx(4.72, abs=0.005)
    assert oc.expert_params(CFG) / million == pytest.approx(37.75, abs=0.005)
    assert oc.layer_params(CFG, 0) / million == pytest.approx(638.8, abs=0.05)
    assert oc.layer_params(CFG, 8) / million == pytest.approx(940.8, abs=0.05)
    assert 2 * oc.layer_params(CFG, 8) / 1e9 == pytest.approx(1.88, abs=0.005)
    assert 4 * 2 * oc.layer_params(CFG, 8) / 1e9 == pytest.approx(7.53, abs=0.005)
    assert 2 * 2 * oc.head_params(CFG) / 1e9 == pytest.approx(3.22, abs=0.005)
    assert oc.total_params(CFG) == 4 * oc.layer_params(CFG, 8) + 2 * oc.head_params(CFG)
    assert oc.total_params(CFG) / 1e9 == pytest.approx(5.37, abs=0.005)
    assert 2 * oc.total_params(CFG) / 1e9 == pytest.approx(10.75, abs=0.005)
    assert 2 * oc.total_params(CFG) / 2 ** 30 == pytest.approx(10.0, abs=0.02)
    # all 512 real experts of ONE layer are 38.7 GB: no chip holds a layer whole
    assert 512 * oc.expert_params(CFG) * 2 / 1e9 == pytest.approx(38.7, abs=0.05)
    # the pool: 8 sub-layers x (8 rows x 4096 tokens + 8 scratch pages of 16) x 1152 B
    assert 8 * (8 * 4096 + 8 * 16) * 1152 / 1e9 == pytest.approx(0.30, abs=0.005)
    # a row's 12 picks put 0.125 on this chip's 8 of 768; 8 of 12 fall on real experts
    assert 12 * 8 / 768 == 0.125 and 12 * 512 / 768 == 8


def test_a_three_row_step_needs_7_gb_and_8_6_ms():
    """3 live rows near 1800 cached tokens, one held expert read a layer: the
    dense weights 4 x 1.28 GB, the experts read 0.30, the head 1.61, the
    latent of 5400 tokens in 8 sub-layers 0.05."""
    need = oc.decode_step_bytes(CFG, 3 * 1800, 1.0)
    dense = 4 * oc.layer_params(CFG, 0)
    by_hand = 2 * (dense + 4 * oc.expert_params(CFG) + oc.head_params(CFG)) + 5400 * 8 * 1152
    assert need == pytest.approx(by_hand) and need / 1e9 == pytest.approx(7.07, abs=0.01)
    assert 2 * oc.layer_params(CFG, 0) / 1e9 == pytest.approx(1.28, abs=0.005)
    assert 2 * oc.head_params(CFG) / 1e9 == pytest.approx(1.61, abs=0.005)
    assert need / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(8.6, abs=0.05)
    # an identity pick reads nothing: no experts read is 4 x 75.5 MB less, all 8 held 2.1 GB more
    assert need - oc.decode_step_bytes(CFG, 5400, 0.0) == 4 * 2 * oc.expert_params(CFG)
    assert oc.decode_step_bytes(CFG, 5400, 8) - need == pytest.approx(4 * 7 * 2 * oc.expert_params(CFG))
    assert oc.decode_step_bytes(CFG, 5400, 50) == oc.decode_step_bytes(CFG, 5400, 8)   # held at most
    # a token's latent is read in BOTH sub-layers of every layer
    assert oc.decode_step_bytes(CFG, 5401, 1.0) - need == 8 * 1152


def test_an_insert_of_2048_tokens_needs_11_tflop():
    one = oc.insert_flops(CFG, [2048], 0.5)
    per_token = 4 * oc.layer_params(CFG, 0) + 0.5 * oc.expert_params(CFG)
    by_hand = (2 * 2048 * per_token + 2 * oc.head_params(CFG)
               + 8 * 2 * 64 * (192 + 128) * 2048 * 2049 / 2)
    assert one == pytest.approx(by_hand) and one / 1e12 == pytest.approx(11.2, abs=0.05)
    assert one / PEAKS["bf16_flops_per_s"] * 1e3 == pytest.approx(57, abs=0.5)
    assert oc.insert_flops(CFG, [900, 1700], 0.5) == pytest.approx(
        oc.insert_flops(CFG, [900], 0.5) + oc.insert_flops(CFG, [1700], 0.5))
    # a pick that costs nothing adds nothing; a held pick adds one expert's product a token
    assert oc.insert_flops(CFG, [2048], 1.5) - one == pytest.approx(2 * 2048 * oc.expert_params(CFG))


# ----------------------------------------------------------------- the readers

def row(first, blocks, prompt):
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


STATS = {"decode_blocks": 3, "kv_walk_steps": 24, "moe_layer_steps": 96,
         "moe_experts_touched": 72, "moe_assignments": 90, "moe_assignments_routed": 8_640,
         "moe_zero_picks": 2_900, "moe_insert_assignments": 2_450,
         "moe_insert_assignments_routed": 235_200, "moe_insert_zero_picks": 78_000,
         "moe_insert_rows": 9_800}


def record(cfg=None, stats=True):
    """Two blocks in the traced stretch (10 s, 20 s]: A runs 8 + 3 live steps,
    B 5 (11 live steps in 0.11 s of the fused decode's device time); A's and
    B's inserts lie before it, C's (1500 tokens) inside, 0.060 s of insert."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=2000)
    b = row(9.5, [(11.0, 5)], prompt=1400)
    c = row(12.5, [], prompt=1500)
    return {"rows": [a, b, c], "config": cfg or CFG, "peaks": PEAKS, "chips": 1,
            "traced": [10.0, 20.0], "engine_stats": dict(STATS) if stats else {},
            "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 4096},
            "device_trace": {"devices": 1, "window_s": 10.0, "busy_s": 0.17,
                             "module_s": {"jit_fused_fn": 0.11, "jit_insert_fn": 0.06},
                             "module_calls": {"jit_fused_fn": 2.0, "jit_insert_fn": 1.0}}}


def test_decode_step_share_of_the_peak_by_hand():
    """11 live steps of 10 ms; A's context 2009..2019, B's 1401..1405; 72
    experts touched in 96 live layer-steps: 0.75 read a layer-step."""
    rec = record()
    context = (sum(range(2009, 2020)) + sum(range(1401, 1406))) / 11
    need = oc.decode_step_bytes(CFG, context, 0.75)
    share = harness.read_layer_metric("scmoe.decode_step_mfu_share", rec)
    assert share == pytest.approx(100 * need / 819e9 / 0.010) and 80 < share < 90
    # a step that takes the roofline's time reads 100 %, and no step can take less
    rec["device_trace"]["module_s"]["jit_fused_fn"] = 11 * need / 819e9
    assert harness.read_layer_metric("scmoe.decode_step_mfu_share", rec) == pytest.approx(100.0)
    # the identity picks are in no count: more of them moves nothing
    rec["engine_stats"]["moe_zero_picks"] *= 2
    assert harness.read_layer_metric("scmoe.decode_step_mfu_share", rec) == pytest.approx(100.0)


def test_insert_mfu_share_by_hand():
    """One insert in the stretch, of 1500 real tokens, in 60 ms; the window's
    inserts put 2 450 picks on held experts over 4 900 tokens, 0.5 a token."""
    share = harness.read_layer_metric("scmoe.insert_mfu_share", record())
    flops = oc.insert_flops(CFG, [1500], 2_450 / 4_900)
    assert share == pytest.approx(100 * flops / 197e12 / 0.06) and 60 < share < 75


def test_zero_pick_share_by_hand():
    """Decode: 2 900 of 8 640 picks; inserts: 78 000 of 235 200."""
    share = harness.read_layer_metric("moe.zero_pick_share", record())
    assert share == pytest.approx(100 * 80_900 / 243_840) and 33 < share < 33.4
    only_decode = record()
    del only_decode["engine_stats"]["moe_insert_zero_picks"]
    del only_decode["engine_stats"]["moe_insert_assignments_routed"]
    assert harness.read_layer_metric("moe.zero_pick_share", only_decode) == \
        pytest.approx(100 * 2_900 / 8_640)


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("other", OTHERS)
def test_new_reader_is_silent_on_another_configurations_record(metric, other):
    """Another configuration's router has no identity experts and its program
    no such counters (its ``engine_stats`` read 0 under these names)."""
    rec = record(cfg=config(other))
    rec["engine_stats"].update(moe_zero_picks=0, moe_insert_zero_picks=0)
    assert harness.read_layer_metric(metric, rec) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("lacks", ["every counter", "moe_zero_picks", "moe_layer_steps",
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
    reads = {"scmoe.decode_step_mfu_share": untraced | {"every counter", "moe_layer_steps",
                                                        "the decode's module"},
             "scmoe.insert_mfu_share": untraced | {"every counter", "moe_insert_assignments",
                                                   "the insert's module"},
             "moe.zero_pick_share": {"every counter", "moe_zero_picks"}}
    got = harness.read_layer_metric(metric, rec)
    assert (got is None) == (lacks in reads[metric])


def test_the_local_assignment_share_counts_every_pick_of_the_768_wide_router():
    """The accepted reader's denominator is ``moe_assignments_routed``: every
    top-12 pick of a live row, identity and absent experts included, so at an
    even router it reads 8 / 768 = 1.04 % here: 90 of 8 640 in the record."""
    assert harness.read_layer_metric("moe.local_assignment_share", record()) == \
        pytest.approx(100 * 90 / 8_640) == pytest.approx(100 * 8 / 768)


# --------------------------------------------------- the file and what it promises

def test_the_configuration_states_its_cut_its_deployment_and_its_assumptions():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts"] == list(CFG["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == CFG["source"]
    assert CFG["source"] == \
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json"
    pub = CFG["published"]
    assert (pub["num_layers"], pub["n_routed_experts"]) == (28, 512)
    assert (CFG["num_layers"], CFG["n_routed_experts"]) == (4, 8)
    assert {k for k, v in pub.items() if CFG[k] != v} == {"num_layers", "n_routed_experts"}
    # every number of the catalog row's config, under its own key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        drawn = next(json.loads(line) for line in catalog.read_text().splitlines()
                     if '"LongCat-Flash-Chat"' in line)
        assert drawn["config"] == pub and drawn["source_url"] == CFG["source"]
    # the router keeps its width, its top-12 and its scale; the widths are the published ones
    assert (CFG["router_experts"], CFG["zero_expert_num"], CFG["moe_topk"],
            CFG["routed_scaling_factor"], CFG["num_local_experts"]) == (512, 256, 12, 6, 8)
    assert (CFG["hidden_size"], CFG["ffn_hidden_size"], CFG["expert_ffn_hidden_size"],
            CFG["num_attention_heads"], CFG["vocab_size"]) == (6144, 12288, 2048, 64, 131072)
    assert (CFG["kv_lora_rank"], CFG["q_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"]) == (512, 1536, 128, 64, 128)
    assert CFG["mla_scale_q_lora"] is True and CFG["mla_scale_kv_lora"] is True
    assert CFG["norm_topk_prob"] is False and CFG["router_bias"] is False
    for key in ("norm_topk_prob", "router_bias", "e_score_correction_bias", "rope_convention",
                "mla_scale_q_lora, mla_scale_kv_lora", "router_experts", "experts_held_first",
                "num_local_experts", "max_position_embeddings", "weights"):
        assert key in CFG["assumed"], key
    assert "(hidden_size / rank) ** 0.5" in CFG["assumed"]["mla_scale_q_lora, mla_scale_kv_lora"]
    for said in ("64 chips share each layer", "24 layers", "0.125 picks", "whole (131 072)",
                 "need no exchange", "more than their share"):
        assert said in CFG["deployment"], said
    assert "5.37 B" in CFG["reduced"]["num_layers"] and "10.75 GB" in CFG["reduced"]["num_layers"]
    assert "504 absent" in CFG["reduced"]["n_routed_experts"]
    assert CFG["serving"] == {"max_batch": 8, "page_size": 16, "prefix_cache": True}
    small = CFG["rehearsal"]
    assert (small["num_layers"], small["router_experts"], small["n_routed_experts"],
            small["zero_expert_num"], small["moe_topk"]) == (2, 16, 4, 8, 6)


def test_the_builder_gives_the_program_the_published_shapes():
    from benchmark.drivers import serving

    mcfg = serving.model_config(CFG, False, max_seq_len=4096, remat_policy=None)
    assert (mcfg.num_layers, mcfg.kv_layers, mcfg.num_experts, mcfg.router_experts,
            mcfg.zero_experts, mcfg.top_k) == (4, 8, 8, 512, 256, 12)
    assert (mcfg.hidden_size, mcfg.intermediate_size, mcfg.moe_intermediate_size,
            mcfg.num_heads, mcfg.latent_dim) == (6144, 12288, 2048, 64, 576)
    assert (mcfg.q_lora_scale, mcfg.kv_lora_scale, mcfg.routed_scaling_factor) == (2.0, 12 ** 0.5, 6)
    assert mcfg.rope_scaling is None and mcfg.rope_theta == 10_000_000 and not mcfg.norm_topk_prob
    assert mcfg.kv_leaf_shapes(8)["cached_key"][0][-2:] == (1, 576)
    small = serving.model_config(harness.load_config(
        next(c for c in BENCH["configs"] if c["name"] == CONFIG), rehearse=True), True,
        max_seq_len=1024, remat_policy=None)
    assert (small.num_layers, small.kv_layers, small.num_experts, small.zero_experts) == (2, 4, 4, 8)


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark" / "reference" / "longcat_flash.py").read_text()
    body = text.split('"""', 2)[2]
    assert "neuronx_distributed_tpu" not in body and 'default_matmul_precision("highest")' in body
    assert "pallas" not in body and "flash_attn" not in body and "deepseek" not in body
    for departure in ("rotary convention", "(hidden_size /\n  rank) ** 0.5"):
        assert departure in text.split('"""', 2)[1], departure


def test_the_tolerances_lie_between_the_readings_and_the_control():
    ref = CFG["reference"]
    assert ref["module"] == "longcat_flash"
    for limit, why in ((ref["tolerance"], ref["tolerance_why"]),
                       (ref["tolerance_any"], ref["tolerance_any_why"])):
        assert 0 < limit < 0.1 and "float8" in why and "PR 52" in why and len(why) > 200
    assert ref["tolerance"] < ref["tolerance_any"]


def test_the_mix_is_longctx_latents_lengths_letter_for_letter():
    mix, pair = traffic.load_mix(MIX), traffic.load_mix("longctx-latent")
    assert (mix["loop"], mix["arrivals"], mix["shared_prefix"]) == ("open", {"process": "poisson"}, None)
    assert mix["prompt_tokens"] == [{"weight": 1.0, "dist": "lognormal", "median": 1500,
                                     "sigma": 0.35, "min": 600, "max": 2048}]
    assert mix["answer_tokens"] == [{"weight": 1.0, "dist": "lognormal", "median": 200,
                                     "sigma": 0.5, "min": 64, "max": 512}]
    assert (mix["max_seq_len"], mix["drain_s"], mix["trace_s"]) == (4096, 60, 8)
    for key in ("prompt_tokens", "answer_tokens", "max_seq_len", "drain_s", "trace_s",
                "shared_prefix", "arrivals", "loop", "rehearsal"):
        assert mix[key] == pair[key], key
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert round(mix["rate_per_s"] * BENCH["run_seconds"]) >= 40
    assert "PR 52" in mix["swept"] and "finished change" in mix["swept"]
    # every seed's window offers the same lengths at the same times (Rule 1)
    a, b = (traffic.open_loop(mix, 1000, seed=s, seconds=51.0) for s in (3, 2_147_483_659))
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in b]


def test_the_new_entries_stand_at_the_end_and_list_the_new_cell_only():
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 3] == NEW_METRICS and at == names.index("moe.insert_real_row_share") + 1
    for name in NEW_METRICS:
        assert by_name[name] == {"name": name, "unit": "%", "better": "higher",
                                 "source": by_name[name]["source"], "layer": "model programs",
                                 "moves": "tpot_ms_p50", "workloads": [CELL]}
        assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
    assert [by_name[n]["source"] for n in NEW_METRICS] == ["device_trace"] * 2 + ["program_counter"]
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(CELL) == cells.index("laguna-s-2.1.longctx") + 1
    assert configs.index(CONFIG) == configs.index("laguna-s-2.1") + 1
    cell = BENCH["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    mix = traffic.load_mix(MIX)
    assert f"{mix['rate_per_s']:g}/s" in cell["why"] and f"knee {mix['knee_per_s']:g}" in cell["why"]
    for said in ("batch 8", "1500", "200", "more than their share"):
        assert said in cell["why"], said


@pytest.mark.parametrize("group,name", [("configs", CONFIG), ("workloads", CELL)])
def test_every_line_the_new_entries_say_fits_the_form(group, name):
    """The driver refuses the whole file for one `why` over 200 characters (PR
    52's first check: the configuration's was 205) or with a character that is
    not printable ASCII; ``test_bm_files.py`` holds only the cells' to that."""
    entry = next(e for e in BENCH[group] if e["name"] == name)
    for key in {"why", "source"} & set(entry):
        said = entry[key]
        assert 1 <= len(said) <= 200 and all(32 <= ord(c) < 127 for c in said), (key, len(said))
    assert (ROOT / "BENCHMARK.json").read_text().endswith("}\n")


def test_the_cell_reports_what_the_issue_lists():
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert {"setup.compile_s", "setup.programs", *APPENDED_TO, *NEW_METRICS} <= listed
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in APPENDED_TO:            # appended after the cell that stood last, not inserted
        cells = by_name[name]["workloads"]
        before = "deepseek-v2.longctx" if name.startswith("moe.") else "laguna-s-2.1.longctx"
        assert cells.index(CELL) == cells.index(before) + 1, name
    # its count is DeepSeek-V2's layer / GQA's: not this cell's
    for name in ("decode.latent_roofline_share", "decode.roofline_share",
                 "moe.experts_touched_share", "swa.decode_step_mfu_share"):
        assert CELL not in by_name[name]["workloads"], name
    e2e = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CELL)}
    assert e2e == {"tpot_ms_p50", "setup_s"}
    tpot = next(m for m in BENCH["end_to_end"] if m["name"] == "tpot_ms_p50")["workloads"]
    assert tpot.index(CELL) == tpot.index("laguna-s-2.1.longctx") + 1


# ----------- what the snapshots this PR moved guarded, for the cells that exist

def test_the_real_row_share_entry_stands_and_lists_every_cell_that_holds_a_share():
    """``test_bm_real_rows.py::test_the_entry_stands_at_the_end_and_lists_the_
    cells_that_hold_a_share`` without "the end" and without the closed list:
    PR 51's entry is what it was, with this cell appended, and the cells it
    lists are exactly those whose configuration holds a share."""
    names = [m["name"] for m in BENCH["per_layer"]]
    entry = BENCH["per_layer"][names.index("moe.insert_real_row_share")]
    assert entry == {"name": "moe.insert_real_row_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model programs",
                     "moves": "tpot_ms_p50",
                     "workloads": ["laguna-s-2.1.longctx", "deepseek-v2.longctx", CELL]}
    held = {w["name"] for w in BENCH["workloads"] if "router_experts" in config(w["config"])}
    assert held == set(entry["workloads"])
    assert names.index("moe.insert_real_row_share") > names.index("swa.window_read_over_needed")


def test_deepseeks_cell_reports_what_it_did():
    """``test_bm_real_rows.py::test_deepseeks_cell_reports_what_it_did_and_the_
    new_share`` with ``moe.local_assignment_share`` listing this cell too."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["decode.latent_roofline_share"]["workloads"] == ["deepseek-v2.longctx"]
    assert by_name["moe.local_assignment_share"]["workloads"] == ["deepseek-v2.longctx", CELL]
    for name in ("decode.latent_roofline_share", "moe.local_assignment_share"):
        assert (by_name[name]["moves"], by_name[name]["layer"], by_name[name]["unit"]) == \
            ("tpot_ms_p50", "model programs", "%")
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", "deepseek-v2.longctx")}
    assert listed == {"ttft_ms_p50", "engine.host_ms_per_block", "engine.batch_occupancy",
                      "engine.slo_attainment", "decode.step_ms", "device.idle_share",
                      "cache.temp_over_pool", "cache.pool_used_peak", "setup.compile_s",
                      "setup.programs", "decode.latent_roofline_share",
                      "moe.local_assignment_share", "moe.insert_real_row_share"}


def test_lagunas_cell_reports_what_it_did():
    """``test_bm_real_rows.py::test_lagunas_cell_reports_what_it_did_and_the_
    new_share`` without "the last of its lists"."""
    cell = "laguna-s-2.1.longctx"
    swa = ["swa.decode_step_mfu_share", "swa.insert_mfu_share", "swa.window_read_over_needed"]
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)}
    assert listed == {
        "setup.compile_s", "setup.programs", "ttft_ms_p50", "engine.host_ms_per_block",
        "engine.batch_occupancy", "engine.slo_attainment", "decode.step_ms", "device.idle_share",
        "cache.temp_over_pool", "cache.pool_used_peak", "engine.admit_ms_per_block",
        "engine.observe_ms_per_block", "engine.launch_ms_per_block", "engine.harvest_ms_per_block",
        "engine.insert_stall_ms_per_block", "engine.queue_wait_ms_mean", *swa,
        "moe.insert_real_row_share"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in swa:
        assert by_name[name]["workloads"] == [cell]
    for name in ("decode.roofline_share", "decode.latent_roofline_share",
                 "moe.local_assignment_share"):
        assert cell not in by_name[name]["workloads"], name
    assert {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", cell)} == \
        {"tpot_ms_p50", "setup_s"}


@pytest.mark.parametrize("other", [c for c in OTHERS if c not in HOLD_A_SHARE])
@pytest.mark.parametrize("metric", ["moe.insert_real_row_share", "moe.local_assignment_share"])
def test_a_held_share_reader_is_silent_where_every_routed_expert_is_held(metric, other):
    """``test_bm_real_rows.py`` / ``test_bm_window.py`` / ``test_bm_latent.py``'s
    cases, for every configuration but the three that hold a share."""
    rec = record(cfg=config(other))
    assert "router_experts" not in rec["config"]
    assert harness.read_layer_metric(metric, rec) is None


def test_the_held_share_readers_read_this_cells_record():
    assert harness.read_layer_metric("moe.insert_real_row_share", record()) == \
        pytest.approx(100 * 2_450 / 9_800)
    # decode.latent_roofline_share counts DeepSeek-V2's layer (one attention, a shared
    # expert, ``num_hidden_layers``): it does not list this cell, and raises on its record
    with pytest.raises(KeyError):
        harness.read_layer_metric("decode.latent_roofline_share", record())


# ------------------------------------------------------------- the rehearsal

def test_the_cells_rehearsal_runs_end_to_end():
    """``run.py --rehearse``: tiny widths on the host, the same control flow as
    the chip run: build, the reference probe, warm-up of every group, a
    window; the new counters tell an identity pick from one that costs."""
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
    assert probe["decode_steps"] == 4 and probe["positions"] == 20
    stats = json.loads((ROOT / "benchmark/out" / f"{CELL}.json").read_text())["record"]["engine_stats"]
    assert 0 < stats["moe_zero_picks"] < stats["moe_assignments_routed"]
    assert stats["moe_assignments"] <= stats["moe_assignments_routed"] - stats["moe_zero_picks"]
    assert 0 < stats["moe_insert_zero_picks"] < stats["moe_insert_assignments_routed"]
    assert stats["moe_insert_assignments"] <= (stats["moe_insert_assignments_routed"]
                                               - stats["moe_insert_zero_picks"])
