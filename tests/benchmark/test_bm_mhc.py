"""The residual streams' own benchmark code, on the CPU: ``opcount_mhc`` against
the hand arithmetic at the published sizes (ISSUE 58's figures), the three
``mhc.*`` readers on hand-made records (silent on every other configuration's
and on a program without what they read), what the configuration file states
and what the two cells promise, and one cell's rehearsal end to end. Every
assertion names the cells and metrics it is about: none counts the cells or
lists a place that a later cell would move (ROADMAP Rule 7). The snapshots this
PR's entries move (``tests/conftest.py``, ``_PR_58_MOVED``) are asserted here,
by name, for today's cells.

No "the shares add up" test is owed: every routed expert is held, no share is taken."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import opcount_mhc as oc
from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
CONFIG, SCORE, CHAT = "xing4.0-29b-a4b", "xing4.0-29b-a4b.score", "xing4.0-29b-a4b.chat"
MIX = "chat-short-xing"
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] != CONFIG]
NEW_METRICS = ["mhc.insert_mfu_share", "mhc.decode_step_mfu_share", "mhc.mix_real_token_share"]
SETUP = ["setup.trace_lower_s", "setup.xla_compile_s", "setup.cache_load_s", "setup.cache_misses",
         "setup.unnamed_compile_s", "setup.format_s", "setup.init_s"]
# what mixtral-8x7b.score lists, its GQA roofline apart
SCORE_LISTS = ["prefill.ms_per_call", "kernels.mosaic_time_share", "device.busy_share",
               "cache.host_ms_per_insert", "engine.inserts_overlapped_share", *SETUP]
# what olmoe-1b-7b.chat lists, its GQA roofline apart
CHAT_LISTS = ["ttft_ms_p50", "ttft_ms_p90", "engine.host_ms_per_block", "engine.batch_occupancy",
              "engine.delivery_gap_ms_p99", "engine.slo_attainment", "decode.step_ms",
              "device.idle_share", "moe.experts_touched_share", "moe.rows_per_touched_expert",
              "engine.admit_ms_per_block", "engine.observe_ms_per_block",
              "engine.launch_ms_per_block", "engine.harvest_ms_per_block",
              "engine.insert_stall_ms_per_block", "engine.queue_wait_ms_mean", *SETUP]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


CFG = config(CONFIG)


# ------------------------------------------------------------------- the count

@pytest.mark.parametrize("count,want", [
    (oc.attention_params, 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064),
    (oc.dense_mlp_params, 3 * 3584 * 9216), (oc.expert_params, 3 * 3584 * 1024),
    (oc.shared_params, 3 * 3584 * 1024), (oc.router_params, 3584 * 64),
    (oc.mix_projection_params, 14_336 * 24), (oc.mix_params, 14_336 * 24 + 27),
    (oc.head_params, 3584 * 131072), (oc.latent_bytes_per_token_layer, 1152),
    (oc.layers, (1, 5)),
], ids=["attention", "dense_mlp", "expert", "shared", "router", "mix_projection", "mix", "head",
        "latent_bytes", "layers"])
def test_count_at_the_published_sizes_is_the_hand_arithmetic(count, want):
    assert count(CFG) == want


def test_the_layers_add_up_to_the_issues_figures():
    """ISSUE 58's cut: a dense layer 128.19 M, an expert layer WHOLE 744.98 M,
    4.79 B parameters = 9.59 GB = 8.93 GiB in bf16."""
    assert oc.dense_layer_params(CFG) == pytest.approx(128.19e6, rel=1e-4)
    assert oc.expert_layer_params(CFG) == pytest.approx(744.98e6, rel=1e-5)
    total = oc.total_params(CFG)
    assert total == 128_188_470 + 5 * 744_980_534 + 2 * 469_762_048
    assert total == pytest.approx(4.79e9, rel=1e-3) and 2 * total / 2 ** 30 == pytest.approx(8.93, abs=0.01)
    # the pool: 1152 B a token-layer x 6 layers x (8 x 1024 tokens + 8 scratch pages of 16)
    assert 1152 * 6 * (8 * 1024 + 8 * 16) == pytest.approx(57e6, rel=0.02)


def test_a_token_of_weights_is_1_1_gflop_and_an_eight_by_512_insert_4_6_tflop():
    assert 2 * oc.token_params(CFG, 4) == pytest.approx(1.10e9, rel=5e-3)
    # the mixes' projections are 12 x 0.69 MFLOP of it
    assert 2 * 12 * oc.mix_projection_params(CFG) == pytest.approx(8.26e6, rel=1e-3)
    one = oc.insert_flops(CFG, [512])
    pair = 2 * 32 * (192 + 128)
    assert one == 2 * 512 * oc.token_params(CFG, 4) + 2 * oc.head_params(CFG) + 6 * pair * 512 * 513 / 2
    assert oc.insert_flops(CFG, [512] * 8) == pytest.approx(4.64e12, rel=2e-3)     # 23.6 ms at the peak
    assert oc.insert_flops(CFG, [300, 400]) == oc.insert_flops(CFG, [300]) + oc.insert_flops(CFG, [400])


def test_a_three_row_step_needs_2_8_gb():
    """~3 live rows read ~11 of 64 experts a layer: 2.81 GB of weights, 3.4 ms
    at the HBM's rate; 900 cached tokens add 6 MB."""
    weights = oc.decode_step_bytes(CFG, 3, 0, 11)
    assert weights == pytest.approx(2.81e9, rel=2e-3)
    assert oc.decode_step_bytes(CFG, 3, 900, 11) - weights == 900 * 6 * 1152
    assert oc.decode_step_bytes(CFG, 8, 0, 11) == weights              # rows are not in the count
    assert oc.decode_step_bytes(CFG, 3, 0, 99) == oc.decode_step_bytes(CFG, 3, 0, 64)
    per_expert = oc.decode_step_bytes(CFG, 3, 0, 12) - weights
    assert per_expert == 5 * 2 * oc.expert_params(CFG)
    # both mixes' projections of all six layers: 8 MB beside the 2.8 GB
    assert 2 * 12 * oc.mix_projection_params(CFG) == pytest.approx(8.26e6, rel=1e-3)


# ----------------------------------------------------------------- the readers

def row(first, blocks, prompt):
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


STATS = {"decode_blocks": 3, "kv_walk_steps": 24, "moe_layer_steps": 120,
         "moe_experts_touched": 1320, "moe_assignments": 1800, "moe_assignments_routed": 1800,
         "moe_insert_assignments": 24_000, "moe_insert_rows": 30_720,
         "mhc_mix_tokens": 12 * 1200, "mhc_mix_slots": 12 * 1536, "mhc_mix_steps": 12 * 32}


def record(cfg=None, stats=True):
    """Two blocks in the traced stretch (10 s, 20 s]: A runs 8 + 3 live steps,
    B 5 (11 live steps in 0.055 s of the fused decode's device time); A's and
    B's inserts lie before it, C's (400 tokens) inside, 0.006 s of insert."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=500)
    b = row(9.5, [(11.0, 5)], prompt=300)
    c = row(12.5, [], prompt=400)
    return {"rows": [a, b, c], "config": cfg or CFG, "peaks": PEAKS, "chips": 1,
            "traced": [10.0, 20.0], "engine_stats": dict(STATS) if stats else {},
            "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 1024},
            "device_trace": {"devices": 1, "window_s": 10.0, "busy_s": 0.061,
                             "module_s": {"jit_fused_fn": 0.055, "jit_insert_fn": 0.006},
                             "module_calls": {"jit_fused_fn": 2.0, "jit_insert_fn": 1.0}}}


def test_decode_step_share_of_the_peak_by_hand():
    """11 live steps of 5 ms; A's context 509..519, B's 301..305; 1320 experts
    touched in 120 live layer-steps: 11 read a layer-step."""
    rec = record()
    context = (sum(range(509, 520)) + sum(range(301, 306))) / 11
    need = oc.decode_step_bytes(CFG, 16 / 11, context, 11)
    share = harness.read_layer_metric("mhc.decode_step_mfu_share", rec)
    assert share == pytest.approx(100 * need / 819e9 / 0.005) and 60 < share < 75
    # a step that takes the roofline's time reads 100 %, and no step can take less
    rec["device_trace"]["module_s"]["jit_fused_fn"] = 11 * need / 819e9
    assert harness.read_layer_metric("mhc.decode_step_mfu_share", rec) == pytest.approx(100.0)


def test_insert_mfu_share_by_hand():
    """One insert in the stretch, of 400 real tokens, in 6 ms."""
    share = harness.read_layer_metric("mhc.insert_mfu_share", record())
    assert share == pytest.approx(100 * oc.insert_flops(CFG, [400]) / 197e12 / 0.006)
    assert 30 < share < 45


def test_the_real_token_share_by_hand():
    rec = record()
    assert harness.read_layer_metric("mhc.mix_real_token_share", rec) == \
        pytest.approx(100 * 1200 / 1536)
    rec["engine_stats"].update(mhc_mix_slots=rec["engine_stats"]["mhc_mix_tokens"])
    assert harness.read_layer_metric("mhc.mix_real_token_share", rec) == pytest.approx(100.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("other", OTHERS)
def test_new_reader_is_silent_on_another_configurations_record(metric, other):
    """Another configuration carries one residual stream and its program has no
    such counters (its ``engine_stats`` read 0 under these names)."""
    rec = record(cfg=config(other))
    rec["engine_stats"].update(mhc_mix_tokens=0, mhc_mix_slots=0, mhc_mix_steps=0)
    assert harness.read_layer_metric(metric, rec) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("lacks", ["every counter", "mhc_mix_tokens", "mhc_mix_slots",
                                   "mhc_mix_steps", "moe_layer_steps", "the traced stretch",
                                   "the insert's module", "the decode's module",
                                   "an untraced run"])
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
    reads = {"mhc.decode_step_mfu_share": untraced | {"every counter", "mhc_mix_steps",
                                                      "moe_layer_steps", "the decode's module"},
             "mhc.insert_mfu_share": untraced | {"every counter", "mhc_mix_tokens",
                                                 "the insert's module"},
             "mhc.mix_real_token_share": {"every counter", "mhc_mix_tokens", "mhc_mix_slots"}}
    got = harness.read_layer_metric(metric, rec)
    assert (got is None) == (lacks in reads[metric])


# --------------------------------------------------- the file and what it promises

def test_the_configuration_states_its_cut_its_deployment_and_its_assumptions():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    cut = ["num_hidden_layers", "first_k_dense_replace"]
    assert entry["reduced"] == cut == list(CFG["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == CFG["source"]
    assert CFG["source"] == \
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"]) == (40, 2)
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"]) == (6, 1)
    assert {k for k, v in pub.items() if CFG[k] != v} == set(cut)
    # every number of the catalog row's config, under its own key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        drawn = next(json.loads(line) for line in catalog.read_text().splitlines()
                     if '"name": "Xing4.0-29B-A4B"' in line)
        assert drawn["config"] == pub and drawn["source_url"] == CFG["source"]
    # no width, no expert count, no vocabulary is touched
    assert (CFG["hc_mult"], CFG["hc_sinkhorn_iters"], CFG["hc_eps"], CFG["mhc_h_res_clamp_min"],
            CFG["mhc_h_res_clamp_max"]) == (4, 20, 1e-6, -30, 30)
    assert (CFG["n_routed_experts"], CFG["num_experts_per_tok"], CFG["n_group"], CFG["topk_group"],
            CFG["routed_scaling_factor"], CFG["n_shared_experts"]) == (64, 4, 1, 1, 2, 1)
    assert (CFG["hidden_size"], CFG["intermediate_size"], CFG["moe_intermediate_size"],
            CFG["num_attention_heads"], CFG["vocab_size"]) == (3584, 9216, 1024, 32, 131072)
    assert (CFG["kv_lora_rank"], CFG["q_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"]) == (512, 768, 128, 64, 128)
    assert (CFG["scoring_func"], CFG["topk_method"], CFG["norm_topk_prob"]) == \
        ("sigmoid", "noaux_tc", True)
    # every expert is held: the keys of a share are not in the file
    assert "router_experts" not in CFG and "experts_held_first" not in CFG
    assert CFG["num_local_experts"] == CFG["num_experts"] == 64
    for key in ("the stream mix", "x~'s norm", "hc_eps in the Sinkhorn", "column before row",
                "the clip", "entry and exit", "coefficient precision", "num_nextn_predict_layers",
                "rope_convention", "head_dim", "ep_size", "max_position_embeddings",
                "e_score_correction_bias", "topk_method, scoring_func", "experts held", "weights"):
        assert key in CFG["assumed"], key
    assert "not built" in CFG["assumed"]["num_nextn_predict_layers"]
    assert "tests/test_xing4.py" in CFG["assumed"]["e_score_correction_bias"]
    for said in ("1 / (nC)", "3 on b_res's diagonal", "NOT symmetric", "that draw is refused"):
        assert said in CFG["assumed"]["weights"], said
    for said in ("each layer is on ONE chip", "all of its 64 experts", "34 layers",
                 "stages of a pipeline", "no pick is dropped"):
        assert said in CFG["deployment"], said
    assert "4.79 B" in CFG["reduced"]["num_hidden_layers"]
    assert "9.59 GB" in CFG["reduced"]["num_hidden_layers"]
    assert CFG["serving"] == {"max_batch": 8, "page_size": 16, "prefix_cache": True}
    small = CFG["rehearsal"]
    assert (small["hc_mult"], small["hc_sinkhorn_iters"], small["num_hidden_layers"],
            small["n_routed_experts"], small["num_experts_per_tok"]) == (4, 20, 3, 8, 4)


def test_the_builder_gives_the_program_the_published_shapes():
    from benchmark.drivers import serving

    mcfg = serving.model_config(CFG, False, max_seq_len=1024, remat_policy=None)
    assert (mcfg.num_layers, mcfg.first_k_dense, mcfg.num_experts, mcfg.router_experts,
            mcfg.experts_held_first, mcfg.top_k, mcfg.n_group, mcfg.topk_group) == \
        (6, 1, 64, None, 0, 4, 1, 1)
    assert (mcfg.hidden_size, mcfg.intermediate_size, mcfg.moe_intermediate_size,
            mcfg.num_heads, mcfg.latent_dim, mcfg.q_lora_rank) == (3584, 9216, 1024, 32, 576, 768)
    assert (mcfg.hc_mult, mcfg.hc_sinkhorn_iters, mcfg.hc_eps, mcfg.mhc_h_res_clamp_min,
            mcfg.mhc_h_res_clamp_max, mcfg.stream_mixes) == (4, 20, 1e-6, -30, 30, 12)
    assert (mcfg.scoring_func, mcfg.router_selection_bias, mcfg.norm_topk_prob,
            mcfg.routed_scaling_factor) == ("sigmoid", True, True, 2)
    assert mcfg.rope_scaling.factor == 64 and mcfg.rope_scaling.mscale_all_dim == 1
    assert mcfg.kv_leaf_shapes(8)["cached_key"][0][-2:] == (1, 576)


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark" / "reference" / "xing4.py").read_text()
    body = text.split('"""', 2)[2]
    assert "neuronx_distributed_tpu" not in body and 'default_matmul_precision("highest")' in body
    assert "pallas" not in body and "flash_attn" not in body and "fori_loop" not in body
    for said in ("no learned gain", "columns are normalised before rows", "before the ``exp``",
                 "replicated in and summed out", "rotary pairs", "num_nextn_predict_layers"):
        assert said in text.split('"""', 2)[1], said


def test_the_limits_lie_between_the_readings_and_the_control():
    ref = CFG["reference"]
    assert ref["module"] == "xing4"
    lo, hi = ref["readings"]["largest_median"], ref["readings"]["float8_median_smallest"]
    assert lo < ref["tolerance"] < hi
    lo, hi = ref["readings"]["largest_any"], ref["readings"]["float8_any_smallest"]
    assert lo < ref["tolerance_any"] < hi
    for why in (ref["tolerance_why"], ref["tolerance_any_why"]):
        assert "float8" in why and "PR 58" in why and len(why) > 200
    for fault in ("transposed", "one Sinkhorn", "no Sinkhorn", "without its 2", "static mix",
                  "stream 0", "route's scale"):
        assert fault in ref["tolerance_why"], fault


def test_the_mix_is_what_the_issue_gives():
    mix = traffic.load_mix(MIX)
    olmoe = traffic.load_mix("chat-short-olmoe")
    assert (mix["loop"], mix["arrivals"], mix["shared_prefix"]) == ("open", {"process": "poisson"}, None)
    assert mix["prompt_tokens"] == olmoe["prompt_tokens"] == [
        {"weight": 1.0, "dist": "lognormal", "median": 200, "sigma": 0.8, "min": 16, "max": 512}]
    assert mix["answer_tokens"] == olmoe["answer_tokens"] == [
        {"weight": 1.0, "dist": "lognormal", "median": 64, "sigma": 0.6, "min": 16, "max": 256}]
    assert (mix["max_seq_len"], mix["drain_s"], mix["trace_s"]) == (1024, 30, 6)
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert round(mix["rate_per_s"] * BENCH["run_seconds"]) >= 100
    assert "PR 58" in mix["swept"] and "finished change" in mix["swept"]
    # every seed's window offers the same lengths at the same times (Rule 1)
    a, b = (traffic.open_loop(mix, 1000, seed=s, seconds=51.0) for s in (3, 2_147_483_659))
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in b]
    # the scoring cell runs the file the benchmark had, as it was
    score = traffic.load_mix("score-prefill-only")
    assert (score["loop"], score["clients"], score["max_seq_len"]) == ("closed", 16, 1024)
    assert score["prompt_tokens"] == [{"weight": 1.0, "dist": "uniform", "min": 256, "max": 512}]


def test_the_new_entries_stand_after_what_was_there_and_list_the_new_cells_only():
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 3] == NEW_METRICS
    assert at == names.index("dsa.latent_read_over_selected") + 1
    want = {"mhc.insert_mfu_share": ("device_trace", "tokens_per_s", [SCORE]),
            "mhc.decode_step_mfu_share": ("device_trace", "tpot_ms_p50", [CHAT]),
            "mhc.mix_real_token_share": ("program_counter", "tokens_per_s", [SCORE])}
    for name, (source, moves, cells) in want.items():
        assert by_name[name] == {"name": name, "unit": "%", "better": "higher", "source": source,
                                 "layer": "model programs", "moves": moves, "workloads": cells}
        assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(SCORE) == cells.index("deepseek-v3.2.longctx") + 1 == cells.index(CHAT) - 1
    assert configs.index(CONFIG) == configs.index("deepseek-v3.2") + 1
    score, chat = (BENCH["workloads"][cells.index(c)] for c in (SCORE, CHAT))
    assert (score["config"], score["traffic"], score["chips"]) == (CONFIG, "score-prefill-only", 1)
    assert (chat["config"], chat["traffic"], chat["chips"]) == (CONFIG, MIX, 1)
    # two configurations stand under one prompt-only mix, letter for letter
    assert next(w for w in BENCH["workloads"] if w["name"] == "mixtral-8x7b.score")["traffic"] == \
        score["traffic"]
    mix = traffic.load_mix(MIX)
    assert f"{mix['rate_per_s']:g}/s" in chat["why"] and f"knee {mix['knee_per_s']:g}" in chat["why"]
    for said in ("closed loop", "16 clients", "256-512", "12 stream mixes", "64-expert"):
        assert said in score["why"], said
    for said in ("batch 8", "Sinkhorn", "12 x a step", "of 64 experts"):
        assert said in chat["why"], said


@pytest.mark.parametrize("group,name", [("configs", CONFIG), ("workloads", SCORE),
                                        ("workloads", CHAT)])
def test_every_line_the_new_entries_say_fits_the_form(group, name):
    """The driver refuses the whole file for one `why` over 200 characters or
    with a character that is not printable ASCII."""
    entry = next(e for e in BENCH[group] if e["name"] == name)
    for key in {"why", "source"} & set(entry):
        said = entry[key]
        assert 1 <= len(said) <= 200 and all(32 <= ord(c) < 127 for c in said), (key, len(said))
    assert set(entry) == ({"name", "source", "file", "reduced", "why"} if group == "configs"
                          else {"name", "config", "traffic", "chips", "why"})
    assert (ROOT / "BENCHMARK.json").read_text().endswith("}\n")
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024
    for metric in BENCH["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
            assert len(metric["layer"]) <= 200 and len(metric["unit"]) <= 16
    assert len(name) <= 64 and all(c.isalnum() or c in "_.-" for c in name)


def test_the_cells_report_what_the_issue_lists():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", SCORE)}
    assert listed == {"setup.compile_s", "setup.programs", *SCORE_LISTS,
                      "mhc.insert_mfu_share", "mhc.mix_real_token_share"}
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CHAT)}
    assert listed == {"setup.compile_s", "setup.programs", *CHAT_LISTS, "mhc.decode_step_mfu_share"}
    for name in SCORE_LISTS:            # appended after the cells that were there, not inserted
        cells = by_name[name]["workloads"]
        assert SCORE in cells and cells.index(SCORE) > cells.index("mixtral-8x7b.score"), name
    for name in CHAT_LISTS:
        cells = by_name[name]["workloads"]
        assert CHAT in cells and cells.index(CHAT) > cells.index("olmoe-1b-7b.chat"), name
    # GQA's counts know neither latent attention nor the mix; no share, no indexer, no
    # identity expert, no window, no state here
    for name in ("prefill.roofline_share", "decode.roofline_share", "decode.latent_roofline_share",
                 "moe.local_assignment_share", "moe.insert_real_row_share", "moe.zero_pick_share",
                 "dsa.selected_share", "scmoe.insert_mfu_share", "swa.insert_mfu_share",
                 "ssm.insert_mfu_share", "cache.temp_over_pool", "cache.pool_used_peak"):
        assert not {SCORE, CHAT} & set(by_name[name]["workloads"]), name
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", SCORE)} == \
        {"tokens_per_s", "setup_s"}
    assert {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CHAT)} == \
        {"tpot_ms_p50", "setup_s"}
    assert e2e["tokens_per_s"]["workloads"].index(SCORE) > \
        e2e["tokens_per_s"]["workloads"].index("granite-4.0-h-micro.toolcalls")
    assert e2e["tpot_ms_p50"]["workloads"].index(CHAT) > \
        e2e["tpot_ms_p50"]["workloads"].index("deepseek-v3.2.longctx")
    assert "workloads" not in e2e["setup_s"]


def test_the_experts_readers_read_the_chat_cells_record():
    """``moe.experts_touched_share`` goes by the key ``num_experts``, which the
    file states for it (64): 1320 touched in 120 layer-steps of 64."""
    assert harness.read_layer_metric("moe.experts_touched_share", record()) == \
        pytest.approx(100 * 1320 / (120 * 64))
    assert harness.read_layer_metric("moe.rows_per_touched_expert", record()) == \
        pytest.approx(1800 / 1320)
    for held in ("moe.local_assignment_share", "moe.insert_real_row_share"):
        assert harness.read_layer_metric(held, record()) is None        # no share is taken


# ----------- what the snapshots this PR moved guarded, for the cells that exist

def test_the_cells_and_lists_that_stood_stand_as_they_were():
    """``tests/conftest.py::_PR_58_MOVED``: each of the six closed a place or a
    list that this PR's entries extend; what it guarded, by name."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    # test_bm_sparse: the open-loop cells judged on the median time a token, in their order
    tpot = e2e["tpot_ms_p50"]["workloads"]
    assert tpot[:tpot.index(CHAT)] == [
        "mixtral-8x7b.chat", "mistral-7b-v0.3.longctx", "olmoe-1b-7b.chat", "mistral-7b-v0.3.chat",
        "deepseek-v2.longctx", "laguna-s-2.1.longctx", "longcat-flash-chat.longctx",
        "deepseek-v3.2.longctx"]
    assert (e2e["tpot_ms_p50"]["bound"], e2e["tokens_per_s"]["bound"], e2e["setup_s"]["bound"]) == \
        (0.04, 0.015, 0.1)
    open_loop = [w["name"] for w in BENCH["workloads"]
                 if traffic.load_mix(w["traffic"]).get("loop") == "open"]
    assert open_loop == tpot
    # test_bm_window: Granite's cell judged on tokens a second, after the cells before it
    tokens = e2e["tokens_per_s"]["workloads"]
    assert tokens[:tokens.index(SCORE)] == ["mixtral-8x7b.score", "pythia-6.9b.train-tp4",
                                            "granite-4.0-h-micro.toolcalls"]
    # test_bm_hybrid: PR 39's seven phase metrics, together and in order; the host's
    # cache work an insert now read in both scoring cells
    seven = ["engine.admit_ms_per_block", "engine.observe_ms_per_block",
             "engine.launch_ms_per_block", "engine.harvest_ms_per_block",
             "engine.insert_stall_ms_per_block", "engine.queue_wait_ms_mean",
             "cache.host_ms_per_insert"]
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(seven[0])
    assert names[at:at + 7] == seven
    for name in seven:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"]) == ("ms", "lower", "program_span")
    assert by_name["cache.host_ms_per_insert"]["workloads"] == ["mixtral-8x7b.score", SCORE]
    assert (by_name["cache.host_ms_per_insert"]["layer"],
            by_name["cache.host_ms_per_insert"]["moves"]) == ("cache", "tokens_per_s")
    # test_bm_sparse: the overlap entry, now listing both scoring cells; DeepSeek-V3.2's
    # cell and its four metrics as PR 56 left them
    assert by_name["engine.inserts_overlapped_share"] == {
        "name": "engine.inserts_overlapped_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler", "moves": "tokens_per_s",
        "workloads": ["mixtral-8x7b.score", SCORE]}
    for name in ("dsa.decode_step_mfu_share", "dsa.insert_mfu_share", "dsa.selected_share",
                 "dsa.latent_read_over_selected"):
        assert by_name[name]["workloads"] == ["deepseek-v3.2.longctx"]
    v32 = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", "deepseek-v3.2.longctx")}
    assert not v32 & set(NEW_METRICS) and "decode.step_ms" in v32 and "ttft_ms_p90" not in v32
    cells = by_name["decode.step_ms"]["workloads"]
    assert cells.index("deepseek-v3.2.longctx") == cells.index("longcat-flash-chat.longctx") + 1
    v2 = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", "deepseek-v2.longctx")}
    assert not v2 & set(NEW_METRICS) and "decode.latent_roofline_share" in v2


def test_deepseek_v2s_roofline_reader_counts_another_layer_on_this_record():
    """``decode.latent_roofline_share`` goes by ``kv_lora_rank`` and counts
    DeepSeek-V2's layer: on this configuration's record it reads a number that
    knows no stream mix, which is why the metric does not list the cell and
    ``mhc.decode_step_mfu_share`` stands beside it."""
    assert harness.read_layer_metric("decode.latent_roofline_share", record()) is not None
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert CHAT not in by_name["decode.latent_roofline_share"]["workloads"]


# ------------------------------------------------------------- the rehearsal

def test_the_scoring_cells_rehearsal_runs_end_to_end():
    """``run.py --rehearse``: tiny widths on the host, the same control flow as
    the chip run: build, the reference probe (no decode step on a one-token
    mix), warm-up of every group, a window; the counters are the host's
    arithmetic."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    got = subprocess.run([sys.executable, "benchmark/run.py", "--workload", SCORE, "--seed",
                          "2147490101", "--seconds", "2", "--trace", "1", "--rehearse"],
                         capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["attempted"] > 0 and line["failed"] == 0
    assert {"mhc.insert_mfu_share", "mhc.mix_real_token_share"} <= set(line["would_report"])
    assert "mhc.decode_step_mfu_share" not in line["would_report"] and line["metrics"] == {}
    assert line["compared"]["logit_gap_max"]["value"] < 1e-5
    probe = next(json.loads(l) for l in got.stdout.splitlines() if '"phase": "reference"' in l)
    assert probe["decode_steps"] == 0 and probe["positions"] == 4
    rec = json.loads((ROOT / "benchmark/out" / f"{SCORE}.json").read_text())["record"]
    stats = rec["engine_stats"]
    mixes = 2 * 3                                   # the rehearsal's three layers
    done = sum(r["prompt_tokens"] for r in rec["rows"] if r["stamps"])
    assert stats["mhc_mix_tokens"] == mixes * done and stats["mhc_mix_steps"] == 0
    assert stats["mhc_mix_slots"] == mixes * 128 * stats["inserted_requests"]       # one bucket
    assert 50 < harness.read_layer_metric("mhc.mix_real_token_share", rec) < 100
    assert stats["decode_blocks"] == 0
