"""The lightning indexer's own benchmark code, on the CPU: ``opcount_sparse``
against the hand arithmetic at the published sizes (ISSUE 56's figures), the
four ``dsa.*`` readers on hand-made records (silent on every other
configuration's and on a program without what they read), what the
configuration file states and what the cell promises, and the cell's rehearsal
end to end. Every assertion names the cells and metrics it is about: none
counts the cells or lists a place that a later cell would move (ROADMAP Rule
7). The snapshots this PR's entries move (``tests/conftest.py``, ``_PR_56_MOVED``)
are asserted here, by name, for today's cells."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import opcount_latent
from benchmark import opcount_sparse as oc
from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
CONFIG, CELL, MIX = "deepseek-v3.2", "deepseek-v3.2.longctx", "longctx-sparse"
BEFORE = "longcat-flash-chat.longctx"        # the cell that stood last in the lists
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] != CONFIG]
NEW_METRICS = ["dsa.decode_step_mfu_share", "dsa.insert_mfu_share", "dsa.selected_share",
               "dsa.latent_read_over_selected"]
APPENDED_TO = ["ttft_ms_p50", "engine.host_ms_per_block", "engine.batch_occupancy",
               "engine.slo_attainment", "decode.step_ms", "cache.temp_over_pool",
               "cache.pool_used_peak", "device.idle_share", "engine.admit_ms_per_block",
               "engine.observe_ms_per_block", "engine.launch_ms_per_block",
               "engine.harvest_ms_per_block", "engine.insert_stall_ms_per_block",
               "engine.queue_wait_ms_mean", "moe.insert_real_row_share",
               "moe.local_assignment_share", "setup.trace_lower_s", "setup.xla_compile_s",
               "setup.cache_load_s", "setup.cache_misses", "setup.unnamed_compile_s",
               "setup.format_s", "setup.init_s"]
HOLD_A_SHARE = ["deepseek-v2", "laguna-s-2.1", "longcat-flash-chat", CONFIG]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


CFG = config(CONFIG)


# ------------------------------------------------------------------- the count

@pytest.mark.parametrize("count,want", [
    (oc.attention_params, 11_010_048 + 37_748_736 + 4_128_768 + 16_777_216 + 117_440_512),
    (oc.indexer_params, 12_582_912 + 917_504 + 458_752),
    (oc.dense_mlp_params, 3 * 7168 * 18432), (oc.expert_params, 3 * 7168 * 2048),
    (oc.shared_params, 3 * 7168 * 2048), (oc.router_params, 7168 * 256),
    (oc.head_params, 7168 * 129280), (oc.latent_bytes_per_token_layer, 1152),
    (oc.index_key_bytes_per_token_layer, 256), (oc.layers, (1, 4)),
], ids=["attention", "indexer", "dense_mlp", "expert", "shared", "router", "head",
        "latent_bytes", "index_key_bytes", "layers"])
def test_count_at_the_published_sizes_is_the_hand_arithmetic(count, want):
    assert count(CFG) == want


def test_the_layers_add_up_to_the_issues_figures():
    """ISSUE 56's reckoning: latent attention 187.11 M, the indexer 13.96 M, the
    shared expert and one routed expert 44.04 M each, the router 1.84 M, the
    dense MLP 396.36 M, embedding and head 926.68 M each: 597.4 + 4 x (246.9 +
    352.3) + 1853.4 = 4.85 B = 9.70 GB (9.03 GiB), beside a 0.46 GB pool."""
    million = 1e6
    assert oc.attention_params(CFG) / million == pytest.approx(187.11, abs=0.005)
    assert oc.indexer_params(CFG) / million == pytest.approx(13.96, abs=0.005)
    assert oc.expert_params(CFG) / million == pytest.approx(44.04, abs=0.005)
    assert oc.router_params(CFG) / million == pytest.approx(1.84, abs=0.005)
    assert oc.dense_mlp_params(CFG) / million == pytest.approx(396.36, abs=0.005)
    assert oc.head_params(CFG) / million == pytest.approx(926.68, abs=0.005)
    dense = oc.attention_params(CFG) + oc.indexer_params(CFG) + oc.dense_mlp_params(CFG)
    outside = (oc.attention_params(CFG) + oc.indexer_params(CFG) + oc.shared_params(CFG)
               + oc.router_params(CFG))
    assert dense / million == pytest.approx(597.4, abs=0.05)
    assert outside / million == pytest.approx(246.9, abs=0.1)
    assert 8 * oc.expert_params(CFG) / million == pytest.approx(352.3, abs=0.05)
    assert oc.total_params(CFG) == dense + 4 * (outside + 8 * oc.expert_params(CFG)) \
        + 2 * oc.head_params(CFG)
    assert oc.total_params(CFG) / 1e9 == pytest.approx(4.85, abs=0.005)
    assert 2 * oc.total_params(CFG) / 1e9 == pytest.approx(9.70, abs=0.01)
    assert 2 * oc.total_params(CFG) / 2 ** 30 == pytest.approx(9.03, abs=0.01)
    # all 256 experts of ONE layer are 22.5 GB: no chip holds a layer whole; a
    # 16-chip share with the vocabulary whole is 6.26 B = 12.5 GB
    assert 256 * oc.expert_params(CFG) * 2 / 1e9 == pytest.approx(22.5, abs=0.05)
    sixteen = oc.total_params(CFG) + 4 * 8 * oc.expert_params(CFG)
    assert sixteen / 1e9 == pytest.approx(6.26, abs=0.005)
    # the pool: 5 layers x (8 rows x 8192 tokens + 8 scratch pages of 16) x 1408 B
    assert 5 * (8 * 8192 + 8 * 16) * (1152 + 256) / 1e9 == pytest.approx(0.46, abs=0.005)
    # of a step's 8 rows x 8 picks, 8 / 256 fall on this chip's 8: 2 a layer-step
    assert 8 * 8 * 8 / 256 == 2


def test_an_eight_row_step_needs_5_6_gb_and_6_8_ms():
    """8 live rows near 3200 cached tokens, 2048 of each chosen, two held
    experts read a layer: attention and indexer 2.01 GB, the dense MLP 0.79,
    the shared experts 0.35, the routers 0.015, the experts read 0.70, the
    head 1.85, index keys of 25 600 tokens in 5 layers 0.033, the latent of
    16 384 chosen 0.094."""
    need = oc.decode_step_bytes(CFG, 8 * 3200, 8 * 2048, 2.0)
    by_hand = 2 * (5 * (oc.attention_params(CFG) + oc.indexer_params(CFG))
                   + oc.dense_mlp_params(CFG)
                   + 4 * (oc.shared_params(CFG) + oc.router_params(CFG) + 2 * oc.expert_params(CFG))
                   + oc.head_params(CFG)) + 5 * (25_600 * 256 + 16_384 * 1152)
    assert need == pytest.approx(by_hand) and need / 1e9 == pytest.approx(5.85, abs=0.01)
    assert 2 * 5 * (oc.attention_params(CFG) + oc.indexer_params(CFG)) / 1e9 == \
        pytest.approx(2.01, abs=0.005)
    assert 2 * oc.dense_mlp_params(CFG) / 1e9 == pytest.approx(0.79, abs=0.005)
    assert 2 * 4 * oc.shared_params(CFG) / 1e9 == pytest.approx(0.35, abs=0.005)
    assert 2 * oc.head_params(CFG) / 1e9 == pytest.approx(1.85, abs=0.005)
    assert need / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(7.1, abs=0.05)
    # what a choice saves of the read: the unchosen latents, 1152 B a token-layer
    dense_read = oc.decode_step_bytes(CFG, 8 * 3200, 8 * 3200, 2.0)
    assert dense_read - need == 5 * 8 * (3200 - 2048) * 1152
    # an index key is read for every VISIBLE token, a latent for every chosen one
    assert oc.decode_step_bytes(CFG, 25_601, 16_384, 2.0) - need == 5 * 256
    assert oc.decode_step_bytes(CFG, 25_600, 16_385, 2.0) - need == 5 * 1152
    assert oc.decode_step_bytes(CFG, 25_600, 16_384, 50) == \
        oc.decode_step_bytes(CFG, 25_600, 16_384, 8)                      # held at most
    # DeepSeek-V2's count of the same layer, without the indexer, the keys and the choice
    v2 = opcount_latent.decode_step_bytes(CFG, 8, 8 * 3200, 2.0)
    assert need - v2 == 2 * 5 * oc.indexer_params(CFG) + 5 * (25_600 * 256
                                                              - 8 * (3200 - 2048) * 1152)


def test_an_insert_of_4096_tokens_needs_16_tflop():
    """ISSUE 56: 13.4 TFLOP outside attention for 4096 tokens (70 ms at the
    peak); the chosen pairs and the scored pairs bring it to 16.4."""
    one = oc.insert_flops(CFG, [4096], 1.0)
    per_token = oc.token_params(CFG, 0) + 1.0 * oc.expert_params(CFG)
    outside = 2 * 4096 * per_token + 2 * oc.head_params(CFG)
    assert outside / 1e12 == pytest.approx(13.4, abs=0.15)
    chosen_pairs = 2048 * 2049 / 2 + 2048 * 2048
    scored_pairs = 4096 * 4097 / 2 - 2048 * 2049 / 2
    by_hand = outside + 5 * (2 * 128 * (192 + 128) * chosen_pairs + 2 * 64 * 128 * scored_pairs)
    assert one == pytest.approx(by_hand) and one / 1e12 == pytest.approx(16.4, abs=0.1)
    assert outside / PEAKS["bf16_flops_per_s"] * 1e3 == pytest.approx(69, abs=1.5)
    assert oc.insert_flops(CFG, [2300, 3900], 1.0) == pytest.approx(
        oc.insert_flops(CFG, [2300], 1.0) + oc.insert_flops(CFG, [3900], 1.0))
    # below index_topk nothing is scored and every pair of the triangle is chosen
    short = oc.insert_flops(CFG, [1000], 0.0)
    assert short == pytest.approx(2 * 1000 * oc.token_params(CFG, 0) + 2 * oc.head_params(CFG)
                                  + 5 * 2 * 128 * 320 * 1000 * 1001 / 2)
    assert oc.insert_flops(CFG, [4096], 2.0) - one == pytest.approx(2 * 4096 * oc.expert_params(CFG))


# ----------------------------------------------------------------- the readers

def row(first, blocks, prompt):
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


STATS = {"decode_blocks": 3, "kv_walk_steps": 24, "moe_layer_steps": 96,
         "moe_experts_touched": 144, "moe_assignments": 180, "moe_assignments_routed": 5_760,
         "moe_insert_assignments": 9_800, "moe_insert_assignments_routed": 313_600,
         "moe_insert_rows": 39_200,
         "dsa_tokens_visible": 600_000, "dsa_tokens_selected": 400_000,
         "dsa_latent_slots_read": 1_000_000}


def record(cfg=None, stats=True):
    """Two blocks in the traced stretch (10 s, 20 s]: A runs 8 + 3 live steps,
    B 5 (11 live steps in 0.11 s of the fused decode's device time); A's and
    B's inserts lie before it, C's (3000 tokens) inside, 0.150 s of insert."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=3900)
    b = row(9.5, [(11.0, 5)], prompt=2900)
    c = row(12.5, [], prompt=3000)
    return {"rows": [a, b, c], "config": cfg or CFG, "peaks": PEAKS, "chips": 1,
            "traced": [10.0, 20.0], "engine_stats": dict(STATS) if stats else {},
            "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 8192},
            "device_trace": {"devices": 1, "window_s": 10.0, "busy_s": 0.26,
                             "module_s": {"jit_fused_fn": 0.11, "jit_insert_fn": 0.15},
                             "module_calls": {"jit_fused_fn": 2.0, "jit_insert_fn": 1.0}}}


def test_decode_step_share_of_the_peak_by_hand():
    """11 live steps of 10 ms; A's context 3909..3919, B's 2901..2905; two
    thirds of the window's visible tokens were chosen; 144 experts touched in
    96 live layer-steps: 1.5 read a layer-step."""
    rec = record()
    context = (sum(range(3909, 3920)) + sum(range(2901, 2906))) / 11
    need = oc.decode_step_bytes(CFG, context, context * 2 / 3, 1.5)
    share = harness.read_layer_metric("dsa.decode_step_mfu_share", rec)
    assert share == pytest.approx(100 * need / 819e9 / 0.010) and 60 < share < 75
    # a step that takes the roofline's time reads 100 %, and no step can take less
    rec["device_trace"]["module_s"]["jit_fused_fn"] = 11 * need / 819e9
    assert harness.read_layer_metric("dsa.decode_step_mfu_share", rec) == pytest.approx(100.0)
    # what the step READ under its mask is in no count: more of it moves nothing
    rec["engine_stats"]["dsa_latent_slots_read"] *= 2
    assert harness.read_layer_metric("dsa.decode_step_mfu_share", rec) == pytest.approx(100.0)


def test_insert_mfu_share_by_hand():
    """One insert in the stretch, of 3000 real tokens, in 150 ms; the window's
    inserts put 9 800 picks on held experts over 9 800 tokens, 1.0 a token."""
    share = harness.read_layer_metric("dsa.insert_mfu_share", record())
    flops = oc.insert_flops(CFG, [3000], 1.0)
    assert share == pytest.approx(100 * flops / 197e12 / 0.15) and 30 < share < 45


def test_the_two_counter_readers_by_hand():
    rec = record()
    assert harness.read_layer_metric("dsa.selected_share", rec) == pytest.approx(100 * 2 / 3)
    assert harness.read_layer_metric("dsa.latent_read_over_selected", rec) == pytest.approx(2.5)
    # rows that never pass index_topk: every visible token chosen, no choice at work
    rec["engine_stats"].update(dsa_tokens_selected=600_000)
    assert harness.read_layer_metric("dsa.selected_share", rec) == pytest.approx(100.0)
    # a gather of the chosen alone would read 1.0
    rec["engine_stats"].update(dsa_latent_slots_read=600_000)
    assert harness.read_layer_metric("dsa.latent_read_over_selected", rec) == pytest.approx(1.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("other", OTHERS)
def test_new_reader_is_silent_on_another_configurations_record(metric, other):
    """Another configuration's attention has no indexer and its program no such
    counters (its ``engine_stats`` read 0 under these names)."""
    rec = record(cfg=config(other))
    rec["engine_stats"].update(dsa_tokens_visible=0, dsa_tokens_selected=0,
                               dsa_latent_slots_read=0)
    assert harness.read_layer_metric(metric, rec) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("lacks", ["every counter", "dsa_tokens_visible", "dsa_tokens_selected",
                                   "dsa_latent_slots_read", "moe_layer_steps",
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
    reads = {"dsa.decode_step_mfu_share": untraced | {"every counter", "dsa_tokens_visible",
                                                      "moe_layer_steps", "the decode's module"},
             "dsa.insert_mfu_share": untraced | {"every counter", "dsa_tokens_visible",
                                                 "moe_insert_assignments", "the insert's module"},
             "dsa.selected_share": {"every counter", "dsa_tokens_visible", "dsa_tokens_selected"},
             "dsa.latent_read_over_selected": {"every counter", "dsa_tokens_selected",
                                               "dsa_latent_slots_read"}}
    if metric == "dsa.decode_step_mfu_share" and lacks == "dsa_tokens_selected":
        with pytest.raises(KeyError):       # a program with one of the pair and not the other
            harness.read_layer_metric(metric, rec)
        return
    got = harness.read_layer_metric(metric, rec)
    assert (got is None) == (lacks in reads[metric])


# --------------------------------------------------- the file and what it promises

def test_the_configuration_states_its_cut_its_deployment_and_its_assumptions():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    cut = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts"]
    assert entry["reduced"] == cut == list(CFG["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == CFG["source"]
    assert CFG["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json"
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"], pub["n_routed_experts"]) == \
        (61, 3, 256)
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"], CFG["n_routed_experts"]) == \
        (5, 1, 8)
    assert {k for k, v in pub.items() if CFG[k] != v} == set(cut)
    # every number of the catalog row's config, under its own key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        drawn = next(json.loads(line) for line in catalog.read_text().splitlines()
                     if '"name": "DeepSeek-V3.2"' in line)
        assert drawn["config"] == pub and drawn["source_url"] == CFG["source"]
    # no width is touched: the indexer, the route and the latent attention as published
    assert (CFG["index_topk"], CFG["index_n_heads"], CFG["index_head_dim"]) == (2048, 64, 128)
    assert (CFG["router_experts"], CFG["n_group"], CFG["topk_group"], CFG["num_experts_per_tok"],
            CFG["routed_scaling_factor"], CFG["num_local_experts"]) == (256, 8, 4, 8, 2.5, 8)
    assert (CFG["hidden_size"], CFG["intermediate_size"], CFG["moe_intermediate_size"],
            CFG["num_attention_heads"], CFG["vocab_size"]) == (7168, 18432, 2048, 128, 129280)
    assert (CFG["kv_lora_rank"], CFG["q_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"]) == (512, 1536, 128, 64, 128)
    assert (CFG["scoring_func"], CFG["topk_method"], CFG["norm_topk_prob"]) == \
        ("sigmoid", "noaux_tc", True)
    for key in ("the indexer", "index keys", "num_nextn_predict_layers", "rope_convention",
                "router_experts", "experts_held_first", "num_local_experts",
                "e_score_correction_bias", "topk_method, scoring_func", "weights"):
        assert key in CFG["assumed"], key
    assert "Hadamard" in CFG["assumed"]["index keys"] and "FP8" in CFG["assumed"]["index keys"]
    assert "not built" in CFG["assumed"]["num_nextn_predict_layers"]
    assert "1 / 1536" in CFG["assumed"]["weights"] and "1 / 7168" in CFG["assumed"]["weights"]
    for said in ("32 chips share each layer", "56 layers", "about 2 of a step's 8 x 8 picks",
                 "whole (129 280)", "without its exchange", "more than their share"):
        assert said in CFG["deployment"], said
    assert "4.85 B" in CFG["reduced"]["num_hidden_layers"]
    assert "9.70 GB" in CFG["reduced"]["num_hidden_layers"]
    assert "248 absent" in CFG["reduced"]["n_routed_experts"]
    assert CFG["serving"] == {"max_batch": 8, "page_size": 16, "prefix_cache": True}
    small = CFG["rehearsal"]
    assert (small["num_hidden_layers"], small["router_experts"], small["n_routed_experts"],
            small["index_topk"], small["num_experts_per_tok"]) == (3, 16, 4, 32, 4)
    lo = traffic.length_range(traffic.load_mix(MIX, rehearse=True)["prompt_tokens"])[0]
    assert small["index_topk"] < lo           # the CPU run chooses in every row


def test_the_builder_gives_the_program_the_published_shapes():
    from benchmark.drivers import serving

    mcfg = serving.model_config(CFG, False, max_seq_len=8192, remat_policy=None)
    assert (mcfg.num_layers, mcfg.first_k_dense, mcfg.num_experts, mcfg.router_experts,
            mcfg.top_k, mcfg.n_group, mcfg.topk_group) == (5, 1, 8, 256, 8, 8, 4)
    assert (mcfg.hidden_size, mcfg.intermediate_size, mcfg.moe_intermediate_size,
            mcfg.num_heads, mcfg.latent_dim) == (7168, 18432, 2048, 128, 576)
    assert (mcfg.index_topk, mcfg.index_n_heads, mcfg.index_head_dim) == (2048, 64, 128)
    assert (mcfg.scoring_func, mcfg.group_score, mcfg.router_selection_bias,
            mcfg.norm_topk_prob, mcfg.routed_scaling_factor) == ("sigmoid", "top2_sum", True, True, 2.5)
    assert mcfg.rope_scaling.factor == 40 and mcfg.rope_scaling.mscale_all_dim == 1
    leaves = mcfg.kv_leaf_shapes(8)
    assert leaves["cached_key"][0][-2:] == (1, 576)
    assert leaves["cached_index_key"][0][-2:] == (1, 128)


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark" / "reference" / "deepseek_v32.py").read_text()
    body = text.split('"""', 2)[2]
    assert "neuronx_distributed_tpu" not in body and 'default_matmul_precision("highest")' in body
    assert "pallas" not in body and "flash_attn" not in body and "top_k(" not in body
    for departure in ("Hadamard", "rotary pairs", "num_nextn_predict_layers", "FP8"):
        assert departure in text.split('"""', 2)[1], departure


def test_the_medians_limit_lies_between_the_readings_and_the_control():
    """The median's limit between the largest reading (0.0196) and the float8
    control's smaller (0.0461); the limit on every position above the largest
    reading (0.0621) and under every visible planted fault's worst position
    (0.113), though NOT under the float8 control's (0.062): the file says so."""
    ref = CFG["reference"]
    assert ref["module"] == "deepseek_v32"
    assert 0.0196 < ref["tolerance"] < 0.0461 and 0.0621 < ref["tolerance_any"] < 0.113
    for why in (ref["tolerance_why"], ref["tolerance_any_why"]):
        assert "float8" in why and "PR 56" in why and len(why) > 200
    assert "does NOT lie between" in ref["tolerance_any_why"]
    for fault in ("no selection", "LOWEST", "no relu", "CANNOT see", "no route scale"):
        assert fault in ref["tolerance_why"], fault


def test_the_mix_is_what_the_issue_gives():
    mix = traffic.load_mix(MIX)
    assert (mix["loop"], mix["arrivals"], mix["shared_prefix"]) == ("open", {"process": "poisson"}, None)
    assert mix["prompt_tokens"] == [{"weight": 1.0, "dist": "lognormal", "median": 3000,
                                     "sigma": 0.25, "min": 2100, "max": 4096}]
    answers, = mix["answer_tokens"]
    assert (answers["dist"], answers["sigma"], answers["min"], answers["max"]) == \
        ("lognormal", 0.35, 96, 640) and answers["median"] in (256, 160)
    assert (mix["max_seq_len"], mix["drain_s"], mix["trace_s"]) == (8192, 60, 8)
    # every prompt lies past index_topk: every decode layer-step scores and chooses
    assert traffic.length_range(mix["prompt_tokens"])[0] > CFG["index_topk"]
    assert traffic.length_range(mix["prompt_tokens"])[1] == 4096         # one bucket
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert round(mix["rate_per_s"] * BENCH["run_seconds"]) >= 40
    assert "PR 56" in mix["swept"] and "finished change" in mix["swept"]
    # every seed's window offers the same lengths at the same times (Rule 1)
    a, b = (traffic.open_loop(mix, 1000, seed=s, seconds=51.0) for s in (3, 2_147_483_659))
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in b]


def test_the_new_entries_stand_at_the_end_and_list_the_new_cell_only():
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 4] == NEW_METRICS
    assert at == names.index("engine.inserts_overlapped_share") + 1
    want = {"dsa.decode_step_mfu_share": ("%", "higher", "device_trace", "model programs"),
            "dsa.insert_mfu_share": ("%", "higher", "device_trace", "model programs"),
            "dsa.selected_share": ("%", "lower", "program_counter", "cache"),
            "dsa.latent_read_over_selected": ("ratio", "lower", "program_counter", "cache")}
    for name, (unit, better, source, layer) in want.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": better, "source": source,
                                 "layer": layer, "moves": "tpot_ms_p50", "workloads": [CELL]}
        assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(CELL) == cells.index(BEFORE) + 1
    assert configs.index(CONFIG) == configs.index("longcat-flash-chat") + 1
    cell = BENCH["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    mix = traffic.load_mix(MIX)
    assert f"{mix['rate_per_s']:g}/s" in cell["why"] and f"knee {mix['knee_per_s']:g}" in cell["why"]
    for said in ("batch 8", "3000", "index_topk 2048", "scores, chooses", "held experts read"):
        assert said in cell["why"], said


@pytest.mark.parametrize("group,name", [("configs", CONFIG), ("workloads", CELL)])
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
    for metric in BENCH["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert len(metric["layer"]) <= 200 and len(metric["unit"]) <= 16


def test_the_cell_reports_what_the_issue_lists():
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert listed == {"setup.compile_s", "setup.programs", *APPENDED_TO, *NEW_METRICS}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in APPENDED_TO:            # appended after the cell that stood last, not inserted
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) == cells.index(BEFORE) + 1 == len(cells) - 1, name
    # their counts know no indexer (or no experts held, or no window): not this cell's
    for name in ("decode.latent_roofline_share", "decode.roofline_share",
                 "scmoe.decode_step_mfu_share", "scmoe.insert_mfu_share", "moe.zero_pick_share",
                 "moe.experts_touched_share", "swa.decode_step_mfu_share"):
        assert CELL not in by_name[name]["workloads"], name
    e2e = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CELL)}
    assert e2e == {"tpot_ms_p50", "setup_s"}
    tpot = next(m for m in BENCH["end_to_end"] if m["name"] == "tpot_ms_p50")["workloads"]
    assert tpot.index(CELL) == tpot.index(BEFORE) + 1


# ----------- what the snapshots this PR moved guarded, for the cells that exist

def test_longcats_cell_reports_what_it_did():
    """``test_bm_scmoe.py``'s places without "the last of its lists": LongCat's
    three metrics list its cell alone, and its cell's set is what PR 54 left."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("scmoe.decode_step_mfu_share", "scmoe.insert_mfu_share", "moe.zero_pick_share"):
        assert by_name[name]["workloads"] == [BEFORE]
    assert by_name["moe.local_assignment_share"]["workloads"] == \
        ["deepseek-v2.longctx", BEFORE, CELL]
    assert by_name["moe.insert_real_row_share"]["workloads"] == \
        ["laguna-s-2.1.longctx", "deepseek-v2.longctx", BEFORE, CELL]
    assert by_name["decode.latent_roofline_share"]["workloads"] == ["deepseek-v2.longctx"]
    mine = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    theirs = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", BEFORE)}
    assert theirs - mine == {"scmoe.decode_step_mfu_share", "scmoe.insert_mfu_share",
                             "moe.zero_pick_share"} and mine - theirs == set(NEW_METRICS)
    held = {w["name"] for w in BENCH["workloads"] if "router_experts" in config(w["config"])}
    assert held == set(by_name["moe.insert_real_row_share"]["workloads"])
    assert {w["config"] for w in BENCH["workloads"] if w["name"] in held} == set(HOLD_A_SHARE)


def test_the_overlap_entry_and_deepseek_v2s_cell_stand_as_they_were():
    """``test_bm_overlap.py::test_the_entry_stands_at_the_end_and_lists_the_
    scoring_cell`` without "the end" (PR 56's four follow it), and
    ``test_bm_startup.py::test_deepseeks_cell_reports_what_it_did`` with
    ``moe.local_assignment_share`` listing this cell too."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["engine.inserts_overlapped_share"] == {
        "name": "engine.inserts_overlapped_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler", "moves": "tokens_per_s",
        "workloads": ["mixtral-8x7b.score"]}
    assert (ROOT / "benchmark/layer_metrics/engine.inserts_overlapped_share.py").is_file()
    for name in ("decode.latent_roofline_share", "moe.local_assignment_share"):
        assert (by_name[name]["moves"], by_name[name]["layer"], by_name[name]["unit"]) == \
            ("tpot_ms_p50", "model programs", "%")
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", "deepseek-v2.longctx")}
    assert listed == {
        "ttft_ms_p50", "engine.host_ms_per_block", "engine.batch_occupancy",
        "engine.slo_attainment", "decode.step_ms", "device.idle_share", "cache.temp_over_pool",
        "cache.pool_used_peak", "setup.compile_s", "setup.programs",
        "decode.latent_roofline_share", "moe.local_assignment_share", "moe.insert_real_row_share",
        "setup.trace_lower_s", "setup.xla_compile_s", "setup.cache_load_s", "setup.cache_misses",
        "setup.unnamed_compile_s", "setup.format_s", "setup.init_s"}


def test_deepseek_v2s_roofline_reader_counts_another_layer_on_this_record():
    """``decode.latent_roofline_share`` goes by ``kv_lora_rank`` and counts
    DeepSeek-V2's layer: on this configuration's record it reads a number that
    knows no indexer, which is why the metric does not list this cell and
    ``dsa.decode_step_mfu_share`` stands beside it."""
    assert harness.read_layer_metric("decode.latent_roofline_share", record()) is not None
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL not in by_name["decode.latent_roofline_share"]["workloads"]


def test_the_median_time_per_token_is_judged_in_the_open_loop_cells_by_name():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["tpot_ms_p50"]["workloads"] == [
        "mixtral-8x7b.chat", "mistral-7b-v0.3.longctx", "olmoe-1b-7b.chat", "mistral-7b-v0.3.chat",
        "deepseek-v2.longctx", "laguna-s-2.1.longctx", BEFORE, CELL]
    assert (e2e["tpot_ms_p50"]["bound"], e2e["tokens_per_s"]["bound"], e2e["setup_s"]["bound"]) == \
        (0.04, 0.015, 0.1)
    open_loop = [w["name"] for w in BENCH["workloads"]
                 if traffic.load_mix(w["traffic"]).get("loop") == "open"]
    assert open_loop == e2e["tpot_ms_p50"]["workloads"]


@pytest.mark.parametrize("other", [c for c in OTHERS if c not in HOLD_A_SHARE])
@pytest.mark.parametrize("metric", ["moe.insert_real_row_share", "moe.local_assignment_share"])
def test_a_held_share_reader_is_silent_where_every_routed_expert_is_held(metric, other):
    rec = record(cfg=config(other))
    assert "router_experts" not in rec["config"]
    assert harness.read_layer_metric(metric, rec) is None


def test_the_held_share_readers_read_this_cells_record():
    assert harness.read_layer_metric("moe.insert_real_row_share", record()) == \
        pytest.approx(100 * 9_800 / 39_200)
    assert harness.read_layer_metric("moe.local_assignment_share", record()) == \
        pytest.approx(100 * 180 / 5_760) == pytest.approx(100 * 8 / 256)


# ------------------------------------------------------------- the rehearsal

def test_the_cells_rehearsal_runs_end_to_end():
    """``run.py --rehearse``: tiny widths on the host, the same control flow as
    the chip run: build, the reference probe, warm-up of every group, a
    window; the new counters say the choice was at work."""
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
    assert min(probe["prompt_lens"]) > 32          # the rehearsal's index_topk
    rec = json.loads((ROOT / "benchmark/out" / f"{CELL}.json").read_text())["record"]
    stats = rec["engine_stats"]
    assert 0 < stats["dsa_tokens_selected"] < stats["dsa_tokens_visible"] \
        < stats["dsa_latent_slots_read"]
    assert stats["dsa_tokens_selected"] % (3 * 32) == 0       # 32 a live row a layer-step
    assert stats["dsa_latent_slots_read"] == 3 * stats["kv_walk_row_slots"]
    assert rec["pool"]["bytes"] == 3 * rec["pool"]["pages"] * 16 * 4 * (32 + 8 + 16)
    assert harness.read_layer_metric("dsa.selected_share", rec) < 30
    assert harness.read_layer_metric("dsa.latent_read_over_selected", rec) > 1
