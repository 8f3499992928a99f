"""The latent-attention (MLA) configuration's own benchmark code, on the CPU:
``opcount_latent`` against the hand arithmetic at the published sizes, the two
readers on hand-made records (and silent on every other configuration's and
on a program without the counters), and what the new cell promises."""

import json
from pathlib import Path

import pytest

from benchmark import opcount_latent as oc
from benchmark import run as harness
from benchmark import traffic

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
CELL = "deepseek-v2.longctx"
OTHERS = [c["name"] for c in BENCH["configs"] if c["name"] != "deepseek-v2"]
NEW_METRICS = ["decode.latent_roofline_share", "moe.local_assignment_share"]


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


CFG = config("deepseek-v2")


# ------------------------------------------------------------------- the count

@pytest.mark.parametrize("count,want", [
    (oc.attention_params, 7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 + 83_886_080),
    (oc.dense_mlp_params, 188_743_680), (oc.shared_params, 47_185_920),
    (oc.router_params, 819_200), (oc.expert_params, 23_592_960),
    (oc.head_params, 524_288_000), (oc.latent_bytes_per_token_layer, 1_152),
    (oc.expert_layer_params, 149_225_472 + 47_185_920 + 819_200 + 20 * 23_592_960),
    (oc.dense_layer_params, 149_225_472 + 188_743_680),
], ids=lambda x: getattr(x, "__name__", None))
def test_count_at_the_published_sizes_is_the_hand_arithmetic(count, want):
    assert count(CFG) == want


def test_the_cut_holds_what_the_configuration_says():
    """1 dense + 5 expert layers as held, embedding and head: 4.73 B
    parameters, 9.46 GB in bf16; the published model's 60 layers of 160
    experts would be 236 B."""
    assert oc.layers(CFG) == (1, 5)
    assert oc.attention_params(CFG) == 149_225_472
    assert oc.total_params(CFG) == 4_731_994_112
    assert oc.total_params(CFG) * 2 / 1e9 == pytest.approx(9.46, abs=0.01)
    whole = dict(CFG, num_hidden_layers=60, n_routed_experts=160)
    assert oc.total_params(whole) / 1e9 == pytest.approx(235.7, abs=0.1)


def test_a_decode_step_of_the_cell_needs_4_2_gb():
    """2.7 live rows of ~1 700 cached tokens, 2 of the 20 held experts read a
    layer-step: six attentions 1 791 MB, the dense MLP 377, five x (shared
    94.4 + router 1.6 + 2 x 47.2) 952, the head 1 049, the latent 32 MB."""
    need = oc.decode_step_bytes(CFG, 2.7, 2.7 * 1700, experts_read=2.0)
    by_hand = (6 * 149_225_472 + 188_743_680 + 5 * (47_185_920 + 819_200 + 2 * 23_592_960)
               + 524_288_000) * 2 + 2.7 * 1700 * 6 * 1152
    assert need == pytest.approx(by_hand) and need / 1e9 == pytest.approx(4.2, abs=0.03)
    assert need / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(5.1, abs=0.05)      # ms
    # the experts read count, up to those held; the rows do not (weights are read once)
    more = oc.decode_step_bytes(CFG, 2.7, 2.7 * 1700, experts_read=20.0)
    assert more - need == pytest.approx(5 * 18 * 23_592_960 * 2)
    assert oc.decode_step_bytes(CFG, 2.7, 2.7 * 1700, experts_read=99.0) == more
    assert oc.decode_step_bytes(CFG, 8.0, 2.7 * 1700, experts_read=2.0) == need
    # the cache is the latent's 1 152 B a token-layer, not the slab's
    assert (oc.decode_step_bytes(CFG, 1.0, 1001.0, 2.0)
            - oc.decode_step_bytes(CFG, 1.0, 1.0, 2.0)) == pytest.approx(1000 * 6 * 1152)


# ----------------------------------------------------------------- the readers

def row(first, blocks, prompt):
    stamps = [first] + [s for s, n in blocks for _ in range(n)]
    return {"due": 0.0, "submitted": 0.0, "stamps": stamps, "failed": False,
            "prompt_tokens": prompt, "want": len(stamps), "why": None}


def record(cfg=None, stats=True):
    """Two blocks in the traced stretch (10 s, 20 s]: A runs 8 + 3 live steps,
    B 5; 11 live steps in 0.088 s of the fused decode's device time."""
    a = row(8.0, [(9.0, 8), (11.0, 8), (12.0, 3)], prompt=1500)
    b = row(10.5, [(11.0, 5)], prompt=900)
    engine_stats = {"decode_blocks": 3, "moe_experts_touched": 190, "moe_layer_steps": 95,
                    "moe_assignments": 120, "moe_assignments_routed": 960}
    return {"rows": [a, b], "config": cfg or CFG, "peaks": PEAKS, "chips": 1,
            "traced": [10.0, 20.0], "engine_stats": engine_stats if stats else {},
            "engine": {"block_steps": 8, "max_batch": 8, "max_seq_len": 4096},
            "device_trace": {"devices": 1, "window_s": 10.0, "busy_s": 0.088,
                             "module_s": {"jit_fused_fn": 0.088},
                             "module_calls": {"jit_fused_fn": 2.0}}}


def test_latent_roofline_share_by_hand():
    """11 live steps of 8 ms; rows (8 + 5 + 3) / 11; A's context 1509..1516
    then 1517..1519, B's 901..905; 190 / 95 = 2 experts a live layer-step."""
    rec = record()
    context = (sum(range(1509, 1520)) + sum(range(901, 906))) / 11
    need = oc.decode_step_bytes(CFG, 16 / 11, context, experts_read=2.0)
    share = harness.read_layer_metric("decode.latent_roofline_share", rec)
    assert share == pytest.approx(100 * need / PEAKS["hbm_bytes_per_s"] / 0.008)
    assert 60 < share < 70
    # the whole step's share: under 100 % unless the step beats the memory roofline
    faster = record()
    faster["device_trace"]["module_s"]["jit_fused_fn"] = 11 * need / PEAKS["hbm_bytes_per_s"]
    assert harness.read_layer_metric("decode.latent_roofline_share", faster) == pytest.approx(100.0)


def test_local_assignment_share_by_hand():
    assert harness.read_layer_metric("moe.local_assignment_share", record()) == pytest.approx(12.5)


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("other", OTHERS)
def test_new_reader_is_silent_on_another_configurations_record(metric, other):
    assert harness.read_layer_metric(metric, record(cfg=config(other))) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
@pytest.mark.parametrize("lacks", ["every counter", "moe_assignments_routed", "moe_layer_steps",
                                   "the traced stretch", "peaks"])
def test_new_reader_is_silent_on_a_program_without_what_it_reads(metric, lacks):
    """The parent's program has neither the counter nor the configuration:
    the reader returns None and never raises (PR 31 was refused for a raise)."""
    rec = record(stats=lacks != "every counter")
    if lacks in rec["engine_stats"]:
        del rec["engine_stats"][lacks]
    if lacks == "the traced stretch":
        rec["device_trace"] = None
    if lacks == "peaks":
        rec["peaks"] = None
    reads = {"decode.latent_roofline_share": {"every counter", "moe_layer_steps",
                                              "the traced stretch", "peaks"},
             "moe.local_assignment_share": {"every counter", "moe_assignments_routed"}}
    got = harness.read_layer_metric(metric, rec)
    assert (got is None) == (lacks in reads[metric])


# -------------------------------------------------------------------- the cell

def test_the_new_metrics_list_the_new_cell_only():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tpot_ms_p50"
        assert by_name[name]["layer"] == "model programs" and by_name[name]["unit"] == "%"
    # GQA's count and the readers of `num_experts` stay with the cells they were written for
    for name in ("decode.roofline_share", "moe.experts_touched_share", "moe.rows_per_touched_expert"):
        assert CELL not in by_name[name]["workloads"]
    listed = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert {"ttft_ms_p50", "engine.host_ms_per_block", "engine.batch_occupancy",
            "engine.slo_attainment", "decode.step_ms", "device.idle_share",
            "cache.temp_over_pool", "cache.pool_used_peak", "setup.compile_s",
            "setup.programs", *NEW_METRICS} == listed


def test_the_median_time_per_token_is_judged_in_the_open_loop_cells_and_no_other():
    """``test_bm_files.py`` holds PR 34's snapshot of this (four cells, by
    count); PR 37 adds the fifth open-loop cell and may not edit that file, so
    the snapshot is marked an expected failure (``conftest.py``) and what it
    guarded is asserted here for the cells of today: ``end_to_end`` is as PR 34
    left it, every open-loop cell is judged on the median time per token under
    4 %, and no other cell is."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    open_loop = sorted(w["name"] for w in BENCH["workloads"]
                       if traffic.load_mix(w["traffic"]).get("loop") == "open")
    assert sorted(e2e["tpot_ms_p50"]["workloads"]) == open_loop
    assert open_loop == sorted(["mixtral-8x7b.chat", "mistral-7b-v0.3.longctx", "olmoe-1b-7b.chat",
                                "mistral-7b-v0.3.chat", CELL])
    assert e2e["tpot_ms_p50"]["workloads"][-1] == CELL          # appended, not inserted
    assert (e2e["tpot_ms_p50"]["bound"], e2e["tpot_ms_p50"]["source"]) == (0.04, "host_clock")
    assert (e2e["tpot_ms_p50"]["unit"], e2e["tpot_ms_p50"]["better"]) == ("ms", "lower")
    assert set(e2e) == {"tpot_ms_p50", "tokens_per_s", "setup_s"}
    assert (e2e["tokens_per_s"]["bound"], e2e["setup_s"]["bound"]) == (0.015, 0.1)
    assert {m["moves"] for m in BENCH["per_layer"]} == set(e2e)


def test_the_configuration_states_its_cut_and_its_deployment():
    entry = next(c for c in BENCH["configs"] if c["name"] == "deepseek-v2")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"]) == (60, 160)
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"]) == (6, 20)
    # the router's width, the groups and every width are as published
    assert CFG["router_experts"] == pub["n_routed_experts"] and "router_experts" not in pub
    assert CFG["n_routed_experts"] * pub["n_group"] == pub["n_routed_experts"]
    assert CFG["num_local_experts"] == CFG["n_routed_experts"] and CFG["experts_held_first"] == 0
    for key in ("n_group", "topk_group", "num_experts_per_tok", "vocab_size", "rope_scaling"):
        assert CFG[key] == pub[key]
    assert "8 chips" in CFG["deployment"] and "vocabulary" in CFG["deployment"]
    assert {"router_experts", "experts_held_first", "num_local_experts", "weights"} <= set(CFG["assumed"])
    # the rehearsal overrides every key that depends on another
    small = CFG["rehearsal"]
    assert small["n_routed_experts"] * small["n_group"] == small["router_experts"]
    assert small["topk_group"] <= small["n_group"] and small["first_k_dense_replace"] < small["num_hidden_layers"]
    assert small["num_local_experts"] == small["n_routed_experts"]
    assert small["num_experts_per_tok"] <= small["topk_group"] * small["n_routed_experts"]


def test_the_mix_is_longctx_decodes_prompts_with_twice_the_answers():
    mix, control = traffic.load_mix("longctx-latent"), traffic.load_mix("longctx-decode")
    assert mix["prompt_tokens"] == control["prompt_tokens"]
    assert mix["max_seq_len"] == control["max_seq_len"] == 4096 and mix["shared_prefix"] is None
    (answers,), (theirs,) = mix["answer_tokens"], control["answer_tokens"]
    assert all(answers[k] == 2 * theirs[k] for k in ("median", "min", "max"))
    assert traffic.length_range(mix["prompt_tokens"]) == (600, 2048)
