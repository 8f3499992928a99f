"""bench.main()'s report assembly, driven with mocked measurement sections
(no TPU): the driver's one-shot BENCH artifact depends on this code path,
which the CPU-smoke branch never executes — a NameError here would end a
round with no artifact at all.

Artifact protocol (VERDICT r5 weak #1 / next #2): the FULL report is written
to a BENCH_REPORT.json sidecar and stdout's final line is a compact
headline-keys-only JSON object, so a 2000-byte tail capture always parses.
"""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402


class _FakeCfg:
    hidden_size = 4096
    intermediate_size = 11008
    vocab_size = 32000
    num_heads = 32
    head_dim_ = 128


def _run_main(monkeypatch, capsys, tmp_path, times, skipped=()):
    monkeypatch.setenv("BENCH_REPORT_PATH", str(tmp_path / "BENCH_REPORT.json"))
    monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bench, "bench_train", lambda **kw: {
        "times": dict(times),
        "mem_L2": 123,
        "lcfg": _FakeCfg(),
        "skipped": list(skipped),
        "visits": {L: 3 for L in times},
        "windows_per_visit": 2,
    })
    monkeypatch.setattr(bench, "bench_inference_ttft",
                        lambda **kw: {"ttft_ms_13b_projected_minfit": 400.0})
    monkeypatch.setattr(bench, "bench_speculation",
                        lambda **kw: {"spec_round_device_ms": 40.0,
                                      "spec_speedup_fused_int8draft2L": 1.42})
    monkeypatch.setattr(bench, "bench_serving",
                        lambda **kw: {"serve_tokens_per_sec_cb": 512.0,
                                      "serve_insert_ms_1slot": 21.0,
                                      "serve_insert_fullwidth_ms_1slot": 60.0,
                                      "serve_fused_round_device_ms": 130.0,
                                      "serve_fused_vs_generate_fused16": 1.05,
                                      "serve_cold_ttft_ms": 95.0,
                                      "serve_prefix_hit_ttft_ms": 24.0,
                                      "serve_prefix_hit_ttft_ratio": 0.253,
                                      "paged_hbm_bytes_vs_slab": 0.542,
                                      "serve_tokens_per_sec_paged": 498.0,
                                      "serve_prefix_hit_ttft_ms_tiered": 41.0,
                                      "tier_restore_ms_p99": 6.3,
                                      "serve_shed_rate_poolpressure": 0.66,
                                      "serve_shed_rate_poolpressure_tiered": 0.56,
                                      "serve_tier_restored_pages": 18,
                                      "serve_itl_p50_ms": 6.2,
                                      "serve_itl_p99_ms": 9.8,
                                      "serve_itl_p99_ms_unchunked": 61.0,
                                      "serve_decode_stall_ms_longprompt": 58.0,
                                      "serve_decode_stall_ms_longprompt_chunked": 9.5,
                                      "serve_itl_p50_ms_disagg": 5.9,
                                      "serve_itl_p99_ms_disagg": 6.4,
                                      "serve_decode_stall_ms_longprompt_disagg": 0.4,
                                      "serve_itl_p99_ms_disagg_inproc": 11.2,
                                      "serve_disagg_handoffs": 10,
                                      "serve_goodput_1x": 540.0,
                                      "serve_goodput_2x_overload": 512.0,
                                      "serve_goodput_2x_vs_1x": 0.948,
                                      "serve_deadline_miss_rate_shed": 0.41,
                                      "serve_deadline_miss_rate_noshed": 0.72,
                                      "serve_recovery_replay_ms": 118.0,
                                      "serve_agg_goodput_2x_n4": 1980.0,
                                      "serve_agg_goodput_2x_n4_rr": 1710.0,
                                      "serve_tenant_p99_fairness_ratio": 1.08,
                                      "serve_failover_replay_ms": 145.0,
                                      "serve_drain_ms": 96.0,
                                      "serve_goodput_autoscale_vs_fixed": 1.21,
                                      "serve_scaleup_time_to_ready_blocks": 0.0,
                                      "serve_autoscale_scale_ups": 3,
                                      "serve_autoscale_scale_downs": 1,
                                      "serve_autoscale_warm_spawns": 1,
                                      "serve_scaleup_spawn_ms": 99.2,
                                      "serve_tokens_per_sec_multilora": 481.0,
                                      "serve_tokens_per_sec_merged_single": 503.0,
                                      "serve_multilora_vs_merged": 0.956,
                                      "adapter_switch_overhead_ms": 3.4,
                                      "adapter_acquire_hit_ms": 0.2,
                                      "adapter_bytes_per_slot": 13371392,
                                      "serve_structured_parse_rate": 1.0,
                                      "serve_itl_p50_ms_structured_vs_freeform": 0.981,
                                      "grammar_compile_ms": 412.5,
                                      "serve_itl_p50_ms_structured": 6.4,
                                      "serve_itl_p50_ms_freeform": 6.28,
                                      "serve_structured_requests": 6,
                                      "grammar_bytes_per_slot": 15360000,
                                      "serve_tokens_per_sec_paged_kernel": 455.0,
                                      "paged_hbm_bytes_vs_slab_int8": 0.14,
                                      "serve_greedy_match_rate_int8kv": 1.0,
                                      "paged_hbm_bytes_int8": 429312,
                                      "serve_paged_kernel_host_ops_per_block": 2.0,
                                      "serve_paged_kernel_basis": "12 reqs",
                                      "serve_tokens_per_sec_tp1": 500.0,
                                      "serve_tokens_per_sec_tp2": 905.0,
                                      "serve_tp2_vs_tp1": 1.81,
                                      "serve_kv_pool_capacity_x_tp": 2.0,
                                      "serve_tp2_stream_equal": True,
                                      "serve_tp_basis": "8 virtual cpu",
                                      "router_sched_overhead_us_per_request": 62.0,
                                      "router_sched_overhead_us_per_request_1k": 55.0,
                                      "router_sched_overhead_us_per_request_100k": 60.0,
                                      "router_sched_overhead_scaling_ratio": 1.13,
                                      "soak_rss_mb_per_100k_requests": 0.0,
                                      "soak_rss_mb_peak": 145.2,
                                      "serve_tracing_overhead_ratio": 0.993,
                                      "serve_tokens_per_sec_traced": 508.4,
                                      "serve_tokens_per_sec_untraced": 512.0,
                                      "compile_ms_by_program": {
                                          "session_fused_k16": 1843.2,
                                          "insert_prefill_r1_b128": 512.7,
                                          "decode": 401.3}})
    import neuronx_distributed_tpu.utils.cp_microbench as cpm
    monkeypatch.setattr(cpm, "measure_cp_ratio_isolated", lambda *a, **kw: {
        "cp_vs_sp_throughput": 0.97, "cp_vs_sp_throughput_ici_serial": 0.95,
        "note": "n", "cp_attempts": 1, "cp_isolated": True})
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, f"bench must print exactly ONE line, got {len(out)}"
    headline = json.loads(out[-1])
    full = json.loads((tmp_path / "BENCH_REPORT.json").read_text())
    return full, headline


def test_report_r5_shape(monkeypatch, capsys, tmp_path):
    d, h = _run_main(monkeypatch, capsys, tmp_path,
                     {0: 0.1147, 1: 0.2630, 2: 0.4634},
                     skipped=[{"depth": 3, "pass": 0, "error": "OOM"}])
    assert d["metric"] == "llama2_7b_train_tokens_per_sec_per_chip"
    assert d["train_measured"] is True
    assert d["vs_baseline"] == pytest.approx(2881.9 / 1687.5, abs=2e-3)
    assert d["train_fit_residual_ms"] == pytest.approx(17.37, abs=0.05)
    assert d["train_L0_excess_ms"] == pytest.approx(52.1, abs=0.1)
    assert d["train_vs_baseline_conservative"] == pytest.approx(1.499, abs=2e-3)
    assert "zero-layer step costs more" in d["train_fit_note"]
    assert d["train_windows_per_depth"] == {"0": 6, "1": 6, "2": 6}
    assert d["train_skipped_depths"][0]["depth"] == 3
    assert d["cp2_zigzag_vs_sp_flash_throughput_16k"] == 0.97
    assert d["cp2_isolated"] is True
    assert d["spec_round_device_ms"] == 40.0
    assert d["mfu_L2_measured"] > 0 and d["step_time_L1_s"] == 0.263
    # headline: the same headline keys, SHORT (tail-capture-proof), pointing
    # at the sidecar; long keys (unit, per-depth dicts) stay out of it
    assert h["value"] == d["value"] and h["vs_baseline"] == d["vs_baseline"]
    assert h["spec_speedup_fused_int8draft2L"] == 1.42
    # serving keys (ISSUE 2) ride both surfaces
    assert d["serve_tokens_per_sec_cb"] == h["serve_tokens_per_sec_cb"] == 512.0
    assert h["serve_insert_ms_1slot"] == 21.0
    # the full-width contrast basis is sidecar-only since ISSUE 14
    assert h["serve_insert_ms_1slot"] < d["serve_insert_fullwidth_ms_1slot"]
    assert "serve_insert_fullwidth_ms_1slot" not in h
    assert h["serve_fused_round_device_ms"] == 130.0
    # paged serving keys (ISSUE 3): prefix-hit TTFT must undercut cold TTFT
    # on both surfaces, and the HBM ratio rides the headline
    assert d["serve_prefix_hit_ttft_ms"] == h["serve_prefix_hit_ttft_ms"] == 24.0
    assert h["serve_prefix_hit_ttft_ms"] < h["serve_cold_ttft_ms"]
    assert h["serve_prefix_hit_ttft_ratio"] == 0.253
    assert h["paged_hbm_bytes_vs_slab"] == 0.542
    assert h["serve_tokens_per_sec_paged"] == 498.0
    # chunked-prefill keys (ISSUE 4): ITL under load + the long-prompt
    # decode stall, chunked vs unchunked, on both surfaces — with chunking
    # beating the one-shot insert on both the p99 and the stall
    assert d["serve_itl_p99_ms"] == h["serve_itl_p99_ms"] == 9.8
    assert h["serve_itl_p50_ms"] == 6.2
    assert h["serve_itl_p99_ms"] < d["serve_itl_p99_ms_unchunked"]
    assert "serve_itl_p99_ms_unchunked" not in h
    assert h["serve_decode_stall_ms_longprompt_chunked"] == 9.5
    # the unchunked stall (contrast basis) is sidecar-only since ISSUE 16
    # (headline size cap — the chunked claim key still gates)
    assert h["serve_decode_stall_ms_longprompt_chunked"] < \
        d["serve_decode_stall_ms_longprompt"]
    assert "serve_decode_stall_ms_longprompt" not in h
    # disaggregation keys (ISSUE 11): decode ITL with zero prefill sharing
    # must beat the chunked baseline, and the long-prompt stall EXCESS on
    # the decode clock is ~0 — chunking bounds interference,
    # disaggregation removes it. In-process wall + handoff counts stay
    # sidecar-only (caveat trail, not headline)
    assert d["serve_itl_p99_ms_disagg"] == h["serve_itl_p99_ms_disagg"] == 6.4
    assert h["serve_itl_p99_ms_disagg"] < h["serve_itl_p99_ms"]
    assert h["serve_decode_stall_ms_longprompt_disagg"] == 0.4
    assert h["serve_decode_stall_ms_longprompt_disagg"] < 1.0
    assert h["serve_decode_stall_ms_longprompt_disagg"] < \
        h["serve_decode_stall_ms_longprompt_chunked"]
    assert "serve_itl_p99_ms_disagg_inproc" not in h
    assert "serve_disagg_handoffs" not in h
    assert d["serve_disagg_handoffs"] == 10
    # host-tier keys (ISSUE 8): a tiered prefix hit must undercut the cold
    # re-prefill, the pool-pressure shed rate must fall with the tier on,
    # and the restore-latency price tag rides the headline next to them
    assert d["serve_prefix_hit_ttft_ms_tiered"] == \
        h["serve_prefix_hit_ttft_ms_tiered"] == 41.0
    assert h["serve_prefix_hit_ttft_ms_tiered"] < h["serve_cold_ttft_ms"]
    # the untiered shed rate (contrast basis — the tiered one gates) is
    # sidecar-only since ISSUE 17 (headline size cap)
    assert h["serve_shed_rate_poolpressure_tiered"] < \
        d["serve_shed_rate_poolpressure"]
    assert "serve_shed_rate_poolpressure" not in h
    assert h["tier_restore_ms_p99"] == 6.3
    assert "serve_tier_restored_pages" not in h      # sidecar-only detail
    # overload + recovery keys (ISSUE 5): shedding must beat the unbounded
    # queue on deadline-miss rate at 2x overload, goodput must hold within
    # 10% of 1x load, and the crash-recovery replay cost rides the headline
    assert d["serve_goodput_2x_overload"] == h["serve_goodput_2x_overload"]
    # the no-shed miss rate (contrast basis — the shedding one gates) is
    # sidecar-only since ISSUE 17 (headline size cap)
    assert h["serve_deadline_miss_rate_shed"] < \
        d["serve_deadline_miss_rate_noshed"]
    assert "serve_deadline_miss_rate_noshed" not in h
    assert h["serve_goodput_2x_vs_1x"] >= 0.9
    assert h["serve_recovery_replay_ms"] == 118.0
    # the 1x goodput (contrast basis of the 2x-vs-1x ratio, which gates)
    # is sidecar-only since ISSUE 16 (headline size cap)
    assert "serve_goodput_1x" not in h and d["serve_goodput_1x"] == 540.0
    # multi-replica router keys (ISSUE 7): the N=4 aggregate goodput must
    # beat the round-robin baseline on both surfaces, the compliant
    # tenant's p99 fairness ratio stays under the 1.2x isolation bound,
    # and the failover/drain wall costs ride the headline
    assert d["serve_agg_goodput_2x_n4"] == h["serve_agg_goodput_2x_n4"]
    # the round-robin contrast basis is sidecar-only since ISSUE 16
    # (headline size cap — the affinity number still gates)
    assert h["serve_agg_goodput_2x_n4"] > d["serve_agg_goodput_2x_n4_rr"]
    assert "serve_agg_goodput_2x_n4_rr" not in h
    assert h["serve_tenant_p99_fairness_ratio"] <= 1.2
    assert h["serve_failover_replay_ms"] == 145.0
    assert h["serve_drain_ms"] == 96.0
    # autoscaling keys (ISSUE 12): goodput per provisioned replica-block,
    # autoscaled over fixed max-provisioned, must clear 1.0 on the diurnal
    # trace (elasticity tracked load without giving back goodput), and the
    # scale-up time-to-ready rides the headline in deterministic virtual
    # blocks; event counts and the spawn wall cost stay sidecar-only
    assert d["serve_goodput_autoscale_vs_fixed"] == \
        h["serve_goodput_autoscale_vs_fixed"] == 1.21
    assert h["serve_goodput_autoscale_vs_fixed"] >= 1.0
    assert h["serve_scaleup_time_to_ready_blocks"] == 0.0
    assert "serve_autoscale_scale_ups" not in h
    assert "serve_scaleup_spawn_ms" not in h
    assert d["serve_autoscale_scale_ups"] == 3
    assert d["serve_autoscale_warm_spawns"] >= 1
    # multi-LoRA keys (ISSUE 10): the mixed 8-adapter trace must hold >=
    # 0.9x the single-merged baseline, the switch-overhead price tag rides
    # the headline next to it; raw baseline tok/s and the pool sizing unit
    # stay sidecar-only
    assert d["serve_tokens_per_sec_multilora"] == \
        h["serve_tokens_per_sec_multilora"] == 481.0
    assert h["serve_multilora_vs_merged"] >= 0.9
    assert h["adapter_switch_overhead_ms"] == 3.4
    assert h["adapter_switch_overhead_ms"] > d["adapter_acquire_hit_ms"]
    assert "serve_tokens_per_sec_merged_single" not in h
    assert "adapter_bytes_per_slot" not in h
    # structured-decoding keys (ISSUE 13): the parse rate is a correctness
    # gate (exactly 1.0 — every constrained completion parses), the
    # structured-vs-freeform ITL ratio must clear the 0.9 no-stall gate,
    # and the one-time DFA compile cost rides the headline; the raw split
    # ITLs and pool sizing unit stay sidecar-only
    assert d["serve_structured_parse_rate"] == \
        h["serve_structured_parse_rate"] == 1.0
    assert h["serve_itl_p50_ms_structured_vs_freeform"] >= 0.9
    assert h["grammar_compile_ms"] == 412.5
    assert "serve_itl_p50_ms_structured" not in h
    assert "serve_itl_p50_ms_freeform" not in h
    assert "grammar_bytes_per_slot" not in h
    assert d["serve_structured_requests"] == 6
    # observability keys (ISSUE 6): the tracing-overhead ratio rides the
    # headline and must clear the zero-cost gate; the per-program compile
    # timing dict is sidecar-only (long keys stay out of the tail capture)
    assert d["serve_tracing_overhead_ratio"] == \
        h["serve_tracing_overhead_ratio"] == 0.993
    assert h["serve_tracing_overhead_ratio"] >= 0.97
    assert d["compile_ms_by_program"]["session_fused_k16"] == 1843.2
    assert "compile_ms_by_program" not in h
    assert "serve_tokens_per_sec_traced" not in h
    # machine-state record (ISSUE 3 satellite): jax/jaxlib versions + XLA
    # flags land in the SIDECAR for cross-run comparability checks — and
    # stay out of the size-capped headline
    assert d["env"]["jax_version"] and "backend" in d["env"]
    assert "xla_flags" in d["env"] and "jaxlib_version" in d["env"]
    assert "env" not in h
    # the sidecar records its own gate set for scripts/bench_regress.py;
    # the size-capped headline does not carry the list
    assert "value" in d["headline_keys"] \
        and "serve_tracing_overhead_ratio" in d["headline_keys"]
    assert "headline_keys" not in h
    assert h["full_report"] == "BENCH_REPORT.json"
    assert "unit" not in h and "train_step_time_s_measured" not in h
    assert len(json.dumps(h)) < 1900, "headline must survive a 2000-byte tail"


def test_report_two_point_fallback(monkeypatch, capsys, tmp_path):
    # L=0 and L=3 both failed: 2-point fit, zero residual, no L0 keys
    d, _ = _run_main(monkeypatch, capsys, tmp_path, {1: 0.263, 2: 0.463})
    assert d["train_fit_residual_ms"] == 0.0
    assert "train_L0_excess_ms" not in d
    assert "train_fit_note" not in d
    assert d["train_vs_baseline_conservative"] == d["vs_baseline"]


def test_report_catastrophic_sweep_still_emits_one_line(monkeypatch, capsys,
                                                        tmp_path):
    # every L>=1 depth failed (e.g. OOM even at L=1): no per-layer signal
    # exists, but the driver still needs its single JSON line — and the
    # headline must carry NULLs plus train_measured=false, never a 0.0
    # sentinel a downstream aggregator could average in (ADVICE r5 low #1)
    d, h = _run_main(monkeypatch, capsys, tmp_path, {0: 0.1147},
                     skipped=[{"depth": 1, "pass": 0, "error": "OOM"},
                              {"depth": 2, "pass": 0, "error": "OOM"}])
    assert d["metric"] == "llama2_7b_train_tokens_per_sec_per_chip"
    assert d["value"] is None and d["vs_baseline"] is None
    assert d["train_measured"] is False
    assert h["value"] is None and h["train_measured"] is False
    assert "UNMEASURED" in d["unit"]
    assert d["train_skipped_depths"][0]["depth"] == 1
    # what WAS measured must survive into the artifact ...
    assert d["step_time_L0_s"] == 0.1147
    assert d["train_step_time_s_measured"] == {"0": 0.1147}
    # ... and the independent sections still run (mocked here)
    assert d["ttft_ms_13b_projected_minfit"] == 400.0
    assert d["cp2_zigzag_vs_sp_flash_throughput_16k"] == 0.97
    assert d["spec_round_device_ms"] == 40.0
    # no projection-derived keys may leak out of an unmeasured sweep
    assert "mfu_7b_projected" not in d and "train_fit_note" not in d


def test_report_single_surviving_depth_labeled_degraded(monkeypatch, capsys,
                                                        tmp_path):
    # only L=1 survived: the value is naive scaling, and the unit must say
    # so instead of claiming a least-squares fit with a perfect residual
    d, _ = _run_main(monkeypatch, capsys, tmp_path, {1: 0.263},
                     skipped=[{"depth": 0, "pass": 0, "error": "X"},
                              {"depth": 2, "pass": 0, "error": "OOM"}])
    assert d["value"] == pytest.approx(8 * 2048 / (0.263 * 32), abs=0.06)
    assert "DEGRADED" in d["unit"] and "naive per-layer scaling" in d["unit"]
    assert d["train_fit_residual_ms"] is None
    assert "train_fit_note" not in d and "train_L0_excess_ms" not in d
    assert "mfu_7b_projected" not in d  # shares the headline's basis


def test_report_degenerate_lsq_labeled_degraded(monkeypatch, capsys, tmp_path):
    # two depths but L=2 measured FASTER than L=1 (noise): _depth_fit's
    # non-positive-slope fallback scales the deepest point — the unit must
    # not claim a least-squares basis for that value
    d, _ = _run_main(monkeypatch, capsys, tmp_path, {1: 0.50, 2: 0.45})
    assert d["value"] == pytest.approx(8 * 2048 / (0.45 / 2 * 32), abs=0.06)
    assert "DEGRADED" in d["unit"] and "degenerated" in d["unit"]
    assert d["train_fit_residual_ms"] is None
    assert "mfu_7b_projected" not in d  # shares the headline's basis


def test_report_degenerate_lsq_with_valid_cons_fit_emits_no_note(
        monkeypatch, capsys, tmp_path):
    # full LSQ degenerates (L0 outlier drives slope negative) while the
    # L>=1 conservative fit is valid: the L0-deviation note describes "the
    # full LSQ" as the headline basis, which would contradict the DEGRADED
    # unit — conservative keys stay (self-describing), the note must not
    d, _ = _run_main(monkeypatch, capsys, tmp_path, {0: 0.9, 1: 0.5, 2: 0.55})
    assert "DEGRADED" in d["unit"]
    assert "train_tok_s_conservative_Lge1_slope" in d
    assert "train_L0_excess_ms" in d
    assert "train_fit_note" not in d


def test_report_l1_outlier_endorses_lsq(monkeypatch, capsys, tmp_path):
    # inflated L=1 (spike): L0 sits below the L>=1 intercept -> the note
    # must endorse the full LSQ, not the conservative keys
    d, _ = _run_main(monkeypatch, capsys, tmp_path, {0: 0.06, 1: 0.30, 2: 0.40})
    assert d["train_L0_excess_ms"] < -5
    assert "prefer the full-LSQ" in d["train_fit_note"]


# ------------------------------------------- bench_regress gate (ISSUE 9)

import subprocess

REPO = Path(__file__).resolve().parent.parent
REGRESS = REPO / "scripts" / "bench_regress.py"


def _regress(*argv):
    p = subprocess.run([sys.executable, str(REGRESS), *map(str, argv)],
                       capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    summary = json.loads(lines[-1]) if lines else None
    return p.returncode, summary, p.stderr


# headline of a bench.py run as the driver captured the rounds before PR 1
# (those records have left the repository; the values are made up)
_OLD_HEADLINE = {
    "metric": "llama2_7b_train_tokens_per_sec_per_chip", "value": 2500.0,
    "unit": "tokens/s/chip", "vs_baseline": 1.48, "mfu_7b_projected": 0.53,
    "mfu_L2_measured": 0.6, "step_time_L1_s": 0.26, "step_time_L2_s": 0.46,
    "batch": 8, "seq": 2048, "ttft_ms_13b_projected_minfit": 400.0,
    "ttft_ms_13b_projected_p50fit": 445.0, "ttft_fit_residual_ms": 4.0,
    "decode_ms_per_token_13b_projected": 56.0,
    "decode_ms_per_token_13b_projected_int8": 34.0,
    "decode_ms_measured": {"1": 7.1, "2": 7.4, "4": 9.3},
    "cp2_zigzag_vs_sp_flash_throughput_16k": 1.0, "spec_target_layers": 8,
    "spec_draft_layers": 2, "spec_num_draft": 4,
    "spec_draft_propose_ms": 17.0, "spec_verify_chunk_ms": 22.0,
    "spec_round_device_ms": 39.0, "spec_plain_decode_ms": 12.9,
    "spec_acceptance_selfdraft": 1.0, "spec_selfdraft_round_ms_p50": 343.0,
    "spec_speedup_alpha1": 1.6, "spec_speedup_alpha0": 0.27,
    "spec_medusa_tree_ms": 20.4, "spec_medusa_replay_ms": 19.5,
    "spec_medusa_tree_nodes": 7,
}


def _old_round_pair(tmp_path):
    """(r04, r05) shaped like the driver's wrappers of those rounds: r04's
    ``parsed`` holds the headline; r05's tail capture cut the front of the
    line off, so ``parsed`` is null and the gate must salvage the tail."""
    line = json.dumps(_OLD_HEADLINE)
    wrap = {"cmd": "python bench.py", "rc": 0}
    r04, r05 = tmp_path / "BENCH_r04.json", tmp_path / "BENCH_r05.json"
    r04.write_text(json.dumps(
        {**wrap, "n": 4, "tail": line, "parsed": _OLD_HEADLINE}))
    r05.write_text(json.dumps(
        {**wrap, "n": 5, "tail": line[len(line) // 3:], "parsed": None}))
    return r04, r05


def test_bench_regress_committed_r04_r05_passes(tmp_path):
    """The acceptance pair: an r04 -> r05 trajectory must clear the gate
    (r05's tail capture truncated the headline, so the candidate side runs
    in salvage mode — flagged, not fatal)."""
    rc, summary, err = _regress(*_old_round_pair(tmp_path))
    assert rc == 0, err
    assert summary["verdict"] == "pass" and not summary["regressions"]
    assert summary["candidate_salvaged"] is True
    assert summary["baseline_salvaged"] is False
    # gate set came from bench.py's HEADLINE_KEYS (neither artifact
    # predates the sidecar list), and real keys were compared
    assert summary["gate_basis"] == "ast:bench.py"
    assert summary["compared"] >= 10 and summary["gated_keys"] > 30


def test_bench_regress_injected_regression_exits_nonzero(tmp_path):
    base = _OLD_HEADLINE
    cand = dict(base)
    cand["value"] = base["value"] * 0.7          # -30% on the headline
    (tmp_path / "base.json").write_text(json.dumps(base))
    (tmp_path / "cand.json").write_text(json.dumps(cand))
    rc, summary, err = _regress(tmp_path / "base.json",
                                tmp_path / "cand.json")
    assert rc == 1, err
    assert summary["verdict"] == "regress"
    assert [r["key"] for r in summary["regressions"]] == ["value"]
    assert summary["regressions"][0]["direction"] == "higher"


def test_bench_regress_direction_and_tolerance(tmp_path):
    """Direction-of-goodness per key: a FALLING latency and a RISING
    throughput are improvements (exit 0); the reverse beyond tolerance is
    a regression; inside tolerance is noise. The artifact's own
    headline_keys list is the gate set when present."""
    keys = ["serve_itl_p99_ms", "serve_tokens_per_sec_cb"]
    base = {"headline_keys": keys,
            "serve_itl_p99_ms": 10.0, "serve_tokens_per_sec_cb": 500.0,
            "spec_draft_propose_ms": 17.0}       # non-headline: never gates
    better = {"headline_keys": keys, "serve_itl_p99_ms": 7.0,
              "serve_tokens_per_sec_cb": 560.0,
              "spec_draft_propose_ms": 40.0}     # ungated wobble
    noisy = {"headline_keys": keys, "serve_itl_p99_ms": 10.9,
             "serve_tokens_per_sec_cb": 495.0}
    worse = {"headline_keys": keys, "serve_itl_p99_ms": 14.0,
             "serve_tokens_per_sec_cb": 500.0}
    for name, doc in (("base", base), ("better", better),
                      ("noisy", noisy), ("worse", worse)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "better.json")
    assert rc == 0 and summary["counts"]["improved"] == 2
    assert summary["gate_basis"] == "artifact_headline_keys"
    assert summary["counts"].get("regressed_ungated", 0) == 1
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "noisy.json")
    assert rc == 0 and not summary["regressions"]
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "worse.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == "serve_itl_p99_ms"
    # a per-key tolerance override waives the same delta
    rc, _, _ = _regress(tmp_path / "base.json", tmp_path / "worse.json",
                        "--tol", "serve_itl_p99_ms=0.5")
    assert rc == 0
    # strict-missing: dropping a gated key fails the gate
    dropped = {"headline_keys": keys, "serve_tokens_per_sec_cb": 500.0}
    (tmp_path / "dropped.json").write_text(json.dumps(dropped))
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "dropped.json")
    assert rc == 0 and summary["missing_gated"] == ["serve_itl_p99_ms"]
    rc, _, _ = _regress(tmp_path / "base.json", tmp_path / "dropped.json",
                        "--strict-missing")
    assert rc == 1
    # a garbage artifact is a usage error (exit 2), not a pass
    (tmp_path / "junk.json").write_text("[]")
    _assert_junk_exits_2(tmp_path)


def _assert_junk_exits_2(tmp_path):
    p = subprocess.run([sys.executable, str(REGRESS),
                        str(tmp_path / "base.json"),
                        str(tmp_path / "junk.json")],
                       capture_output=True, text=True)
    assert p.returncode == 2


def test_bench_regress_new_keys_never_gate(tmp_path):
    """ISSUE 11 satellite: a baseline that PREDATES a feature's headline
    keys (the committed r05 sidecar predates PRs 6–11's serving keys) must
    never fail the gate over them — candidate-only keys report as an
    explicit ``new_key`` verdict, gated or not."""
    keys = ["serve_itl_p99_ms", "serve_itl_p99_ms_disagg",
            "serve_decode_stall_ms_longprompt_disagg"]
    base = {"headline_keys": keys, "serve_itl_p99_ms": 10.0}
    cand = {"headline_keys": keys, "serve_itl_p99_ms": 9.8,
            "serve_itl_p99_ms_disagg": 6.4,
            "serve_decode_stall_ms_longprompt_disagg": 0.4,
            "serve_disagg_handoffs": 10.0}
    (tmp_path / "base.json").write_text(json.dumps(base))
    (tmp_path / "cand.json").write_text(json.dumps(cand))
    rc, summary, err = _regress(tmp_path / "base.json",
                                tmp_path / "cand.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass" and not summary["regressions"]
    assert summary["counts"]["new_key"] == 3
    # even --strict-missing only guards baseline keys the candidate
    # DROPPED, never keys the baseline predates
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "cand.json", "--strict-missing")
    assert rc == 0 and not summary["missing_gated"]
    # and an r05-shaped artifact (predates the PR 6-10 serving keys in
    # HEADLINE_KEYS) passes as a baseline against a modern-shaped candidate
    rc, summary, err = _regress(_old_round_pair(tmp_path)[1],
                                tmp_path / "cand.json")
    assert rc == 0, err
    assert summary["counts"].get("new_key", 0) >= 2


def test_bench_regress_committed_r06_gates_serving_keys(tmp_path):
    """ISSUE 12 satellite: the committed BENCH_r06 sidecar (CPU basis,
    scripts/bench_cpu_basis.py) carries the PR 4-11 serving keys — which
    the r05 TPU artifact predates — so the regression gate finally has a
    serving baseline: r06 vs itself passes, an injected serving-key
    regression exits 1 naming the key."""
    doc = json.loads((REPO / "BENCH_r06.json").read_text())
    assert doc["n"] == 6 and doc["rc"] == 0
    p = doc["parsed"]
    # the PR 4-12 serving keys that were un-gated before this artifact
    for key in ("serve_itl_p99_ms", "serve_goodput_2x_overload",
                "serve_prefix_hit_ttft_ms_tiered", "serve_multilora_vs_merged",
                "serve_failover_replay_ms", "serve_itl_p99_ms_disagg",
                "serve_goodput_autoscale_vs_fixed",
                "serve_scaleup_time_to_ready_blocks"):
        assert key in p, key
    assert not [k for k in p if k.endswith("_error")], "a section failed"
    assert p["serve_goodput_autoscale_vs_fixed"] >= 1.0
    assert "cpu" in p["serve_cpu_basis"].lower()
    rc, summary, err = _regress(REPO / "BENCH_r06.json",
                                REPO / "BENCH_r06.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass"
    assert summary["gate_basis"] == "artifact_headline_keys"
    bad = dict(doc, parsed=dict(p, serve_goodput_2x_overload=p[
        "serve_goodput_2x_overload"] * 0.5))
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    rc, summary, _ = _regress(REPO / "BENCH_r06.json", tmp_path / "bad.json")
    assert rc == 1
    assert [r["key"] for r in summary["regressions"]] == \
        ["serve_goodput_2x_overload"]


def test_report_sched_soak_keys(monkeypatch, capsys, tmp_path):
    """ISSUE 14 satellite: the fleet-scale scheduler soak keys ride the
    headline (mocked serving section) — the scaling curve's endpoints,
    the sub-linearity ratio and the RSS leak slope all surface, and the
    ratio/slope are the gate-bearing quantities."""
    d, h = _run_main(monkeypatch, capsys, tmp_path,
                     {1: 0.263, 2: 0.463, 3: 0.663, 4: 0.863})
    for key in ("router_sched_overhead_us_per_request",
                "router_sched_overhead_scaling_ratio",
                "soak_rss_mb_per_100k_requests"):
        assert key in h, key
        assert h[key] == d[key]
    # the full curve stays in the SIDECAR (headline is size-capped)
    for key in ("router_sched_overhead_us_per_request_1k",
                "router_sched_overhead_us_per_request_100k"):
        assert key in d and key not in h
    assert h["router_sched_overhead_scaling_ratio"] < 3.0
    assert h["soak_rss_mb_per_100k_requests"] >= 0.0


def test_bench_regress_sched_soak_direction_rules(tmp_path):
    """Direction-of-goodness for the soak keys: a RISING per-request
    overhead, scaling ratio, or RSS slope regresses (lower-is-better all
    three); the overhead keys get the generous shared-box tolerance, the
    ratio the tight algorithmic one."""
    keys = ["router_sched_overhead_us_per_request",
            "router_sched_overhead_scaling_ratio"]
    base = {"headline_keys": keys,
            "router_sched_overhead_us_per_request": 60.0,
            "router_sched_overhead_scaling_ratio": 1.1}
    worse = {"headline_keys": keys,
             "router_sched_overhead_us_per_request": 60.0,
             "router_sched_overhead_scaling_ratio": 2.5}
    noisy = {"headline_keys": keys,
             "router_sched_overhead_us_per_request": 72.0,
             "router_sched_overhead_scaling_ratio": 1.1}
    blown = {"headline_keys": keys,
             "router_sched_overhead_us_per_request": 140.0,
             "router_sched_overhead_scaling_ratio": 1.1}
    for name, doc in (("base", base), ("worse", worse), ("noisy", noisy),
                      ("blown", blown)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "worse.json")
    assert rc == 1
    assert [r["key"] for r in summary["regressions"]] == \
        ["router_sched_overhead_scaling_ratio"]
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "noisy.json")
    assert rc == 0, "20% wall noise must not gate"
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "blown.json")
    assert rc == 1
    assert [r["key"] for r in summary["regressions"]] == \
        ["router_sched_overhead_us_per_request"]


def test_bench_regress_committed_r07_gates_sched_keys(tmp_path):
    """ISSUE 14 satellite: BENCH_r07 (scripts/bench_cpu_basis.py
    --sched-update over r06) carries the fleet-scale scheduler keys with
    the measured sub-linear curve; r07 vs itself passes, r06 -> r07
    reports the sched keys as new_key (never gating), and an injected
    scaling-ratio regression exits 1 naming the key."""
    doc = json.loads((REPO / "BENCH_r07.json").read_text())
    assert doc["rc"] == 0 and "--sched-update" in doc["cmd"]
    p = doc["parsed"]
    for key in ("router_sched_overhead_us_per_request",
                "router_sched_overhead_us_per_request_1k",
                "router_sched_overhead_us_per_request_100k",
                "router_sched_overhead_scaling_ratio",
                "soak_rss_mb_per_100k_requests"):
        assert key in p, key
    assert not [k for k in p if k.endswith("_error")], "a section failed"
    # the acceptance criteria, pinned on the committed artifact: the 1M
    # overhead within 3x of 1k (sub-linear curve) and a flat RSS slope
    assert p["router_sched_overhead_scaling_ratio"] < 3.0
    assert p["soak_rss_mb_per_100k_requests"] < 2.0
    assert "sched_soak_curve" in p and "1000000" in p["sched_soak_curve"]
    rc, summary, err = _regress(REPO / "BENCH_r07.json",
                                REPO / "BENCH_r07.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass"
    rc, summary, _ = _regress(REPO / "BENCH_r06.json",
                              REPO / "BENCH_r07.json")
    assert rc == 0, "new sched keys must land as new_key, never gate"
    bad = dict(doc, parsed=dict(
        p, router_sched_overhead_scaling_ratio=
        p["router_sched_overhead_scaling_ratio"] * 2.5))
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    rc, summary, _ = _regress(REPO / "BENCH_r07.json", tmp_path / "bad.json")
    assert rc == 1
    assert "router_sched_overhead_scaling_ratio" in \
        [r["key"] for r in summary["regressions"]]


def test_bench_regress_committed_r08_gates_structured_keys(tmp_path):
    """ISSUE 15 satellite: BENCH_r08 (scripts/bench_cpu_basis.py
    --structured-update over r07) closes the bench-surface drift
    nxdcheck's surface-drift rule flagged — the three structured
    HEADLINE keys were absent from every committed serving artifact (r06
    predates PR 13; r07 only merged sched keys), so they compared as
    new_key forever and never gated. r08 carries them: self-pass,
    r07 -> r08 lands them as new_key, and an injected parse-rate drop
    exits 1 (zero tolerance — a parse-rate move is a masking bug, not
    noise)."""
    doc = json.loads((REPO / "BENCH_r08.json").read_text())
    assert doc["rc"] == 0 and "--structured-update" in doc["cmd"]
    p = doc["parsed"]
    for key in ("serve_structured_parse_rate",
                "serve_itl_p50_ms_structured_vs_freeform",
                "grammar_compile_ms"):
        assert key in p, key
    assert not [k for k in p if k.endswith("_error")], "a section failed"
    # the structural guarantees, pinned on the committed artifact
    assert p["serve_structured_parse_rate"] == 1.0
    assert p["serve_itl_p50_ms_structured_vs_freeform"] >= 0.9
    rc, summary, err = _regress(REPO / "BENCH_r08.json",
                                REPO / "BENCH_r08.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass"
    rc, summary, _ = _regress(REPO / "BENCH_r07.json",
                              REPO / "BENCH_r08.json")
    assert rc == 0, "structured keys must land as new_key over r07"
    bad = dict(doc, parsed=dict(p, serve_structured_parse_rate=0.96))
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    rc, summary, _ = _regress(REPO / "BENCH_r08.json", tmp_path / "bad.json")
    assert rc == 1
    assert "serve_structured_parse_rate" in \
        [r["key"] for r in summary["regressions"]]


def test_report_tp_keys(monkeypatch, capsys, tmp_path):
    """ISSUE 16 satellite: the TP-sharded-serving keys ride the report
    (mocked serving section) — the TP2/TP1 speedup ratio and per-chip
    KV-pool capacity multiplier are the gate-bearing quantities on the
    headline; the absolute throughputs stay in the sidecar."""
    d, h = _run_main(monkeypatch, capsys, tmp_path,
                     {1: 0.263, 2: 0.463, 3: 0.663, 4: 0.863})
    for key in ("serve_tp2_vs_tp1", "serve_kv_pool_capacity_x_tp"):
        assert key in h, key
        assert h[key] == d[key]
    # absolute throughputs, exactness flag + basis note stay in the
    # SIDECAR (headline is size-capped; the ratio already gates, the
    # absolutes and the flag are forensic)
    for key in ("serve_tokens_per_sec_tp1", "serve_tokens_per_sec_tp2",
                "serve_tp2_stream_equal", "serve_tp_basis"):
        assert key in d and key not in h
    assert d["serve_tp2_stream_equal"] is True
    assert h["serve_kv_pool_capacity_x_tp"] >= 1.9


def test_bench_regress_tp_direction_rules(tmp_path):
    """Direction-of-goodness for the TP keys: a FALLING TP2/TP1 speedup
    or capacity multiplier regresses (higher-is-better both); the speedup
    gets a generous shared-box tolerance, the capacity multiplier a tight
    structural one — halving the pool is geometry, not wall clock."""
    keys = ["serve_tp2_vs_tp1", "serve_kv_pool_capacity_x_tp"]
    base = {"headline_keys": keys,
            "serve_tp2_vs_tp1": 1.8,
            "serve_kv_pool_capacity_x_tp": 2.0}
    worse = {"headline_keys": keys,
             "serve_tp2_vs_tp1": 1.8,
             "serve_kv_pool_capacity_x_tp": 1.5}
    noisy = {"headline_keys": keys,
             "serve_tp2_vs_tp1": 1.45,
             "serve_kv_pool_capacity_x_tp": 2.0}
    blown = {"headline_keys": keys,
             "serve_tp2_vs_tp1": 0.9,
             "serve_kv_pool_capacity_x_tp": 2.0}
    for name, doc in (("base", base), ("worse", worse), ("noisy", noisy),
                      ("blown", blown)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "worse.json")
    assert rc == 1
    assert [r["key"] for r in summary["regressions"]] == \
        ["serve_kv_pool_capacity_x_tp"]
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "noisy.json")
    assert rc == 0, "20% speedup noise must not gate"
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "blown.json")
    assert rc == 1
    assert [r["key"] for r in summary["regressions"]] == ["serve_tp2_vs_tp1"]


def test_bench_regress_committed_r09_gates_tp_keys(tmp_path):
    """ISSUE 16 satellite: BENCH_r09 (scripts/bench_cpu_basis.py
    --tp-update over r08, 8 virtual CPU devices) carries the TP-sharded
    serving keys no prior artifact could (single-device runs). Self-pass,
    r08 -> r09 lands them as new_key, the committed capacity multiplier
    meets the >= 1.9 acceptance bar with streams bit-equal, and an
    injected capacity drop exits 1 naming the key."""
    doc = json.loads((REPO / "BENCH_r09.json").read_text())
    assert doc["rc"] == 0 and "--tp-update" in doc["cmd"]
    p = doc["parsed"]
    for key in ("serve_tokens_per_sec_tp1", "serve_tokens_per_sec_tp2",
                "serve_tp2_vs_tp1", "serve_kv_pool_capacity_x_tp"):
        assert key in p, key
    assert not [k for k in p if k.endswith("_error")], "a section failed"
    # the acceptance criteria, pinned on the committed artifact
    assert p["serve_kv_pool_capacity_x_tp"] >= 1.9
    assert p["serve_tp2_stream_equal"] is True
    rc, summary, err = _regress(REPO / "BENCH_r09.json",
                                REPO / "BENCH_r09.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass"
    rc, summary, _ = _regress(REPO / "BENCH_r08.json",
                              REPO / "BENCH_r09.json")
    assert rc == 0, "new TP keys must land as new_key over r08"
    bad = dict(doc, parsed=dict(p, serve_kv_pool_capacity_x_tp=1.0))
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    rc, summary, _ = _regress(REPO / "BENCH_r09.json", tmp_path / "bad.json")
    assert rc == 1
    assert "serve_kv_pool_capacity_x_tp" in \
        [r["key"] for r in summary["regressions"]]


def test_report_paged_kernel_keys(monkeypatch, capsys, tmp_path):
    """ISSUE 17 satellite: the paged-kernel/int8-KV keys ride the report
    (mocked serving section) — kernel throughput, the int8-vs-slab
    sizing ratio and the zero-tolerance greedy agreement gate from the
    headline; the absolute int8 pool bytes, the host-ops count and the
    basis string stay in the sidecar."""
    d, h = _run_main(monkeypatch, capsys, tmp_path,
                     {1: 0.263, 2: 0.463, 3: 0.663, 4: 0.863})
    for key in ("serve_tokens_per_sec_paged_kernel",
                "paged_hbm_bytes_vs_slab_int8",
                "serve_greedy_match_rate_int8kv"):
        assert key in h, key
        assert h[key] == d[key]
    for key in ("paged_hbm_bytes_int8",
                "serve_paged_kernel_host_ops_per_block",
                "serve_paged_kernel_basis"):
        assert key in d and key not in h
    assert h["serve_greedy_match_rate_int8kv"] == 1.0
    assert h["paged_hbm_bytes_vs_slab_int8"] <= 0.5


def test_bench_regress_paged_kernel_direction_rules(tmp_path):
    """Direction-of-goodness for the paged-kernel keys: kernel tok/s is
    higher-better with throughput noise tolerance; the int8-vs-slab
    sizing ratio is lower-better and tight (it is deterministic at fixed
    dims — only a layout regression moves it); the int8 greedy agreement
    is zero-tolerance (ANY drop means quantization error started
    flipping greedy tokens)."""
    keys = ["serve_tokens_per_sec_paged_kernel",
            "paged_hbm_bytes_vs_slab_int8", "serve_greedy_match_rate_int8kv"]
    base = {"headline_keys": keys,
            "serve_tokens_per_sec_paged_kernel": 450.0,
            "paged_hbm_bytes_vs_slab_int8": 0.14,
            "serve_greedy_match_rate_int8kv": 1.0}
    flipped = dict(base, serve_greedy_match_rate_int8kv=0.996)
    fattened = dict(base, paged_hbm_bytes_vs_slab_int8=0.17)
    noisy = dict(base, serve_tokens_per_sec_paged_kernel=418.0)
    slowed = dict(base, serve_tokens_per_sec_paged_kernel=380.0)
    for name, doc in (("base", base), ("flipped", flipped),
                      ("fattened", fattened), ("noisy", noisy),
                      ("slowed", slowed)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "flipped.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == \
        "serve_greedy_match_rate_int8kv"
    assert summary["regressions"][0]["direction"] == "higher"
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "fattened.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == "paged_hbm_bytes_vs_slab_int8"
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "noisy.json")
    assert rc == 0, "7% throughput noise must not gate"
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "slowed.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == \
        "serve_tokens_per_sec_paged_kernel"


def test_bench_regress_committed_r10_gates_kernel_keys(tmp_path):
    """ISSUE 17 satellite: BENCH_r10 (scripts/bench_cpu_basis.py
    --kernel-update over r09) carries the paged-kernel/int8-KV keys no
    prior artifact could (the kernel and int8 pools postdate r09).
    Self-pass, r09 -> r10 lands them as new_key, the committed values
    meet the acceptance bars (int8 pool <= 0.5x the un-quantized slab,
    greedy agreement exactly 1.0, decode host ops still 2/block), and an
    injected match-rate drop exits 1 naming the key."""
    doc = json.loads((REPO / "BENCH_r10.json").read_text())
    assert doc["rc"] == 0 and "--kernel-update" in doc["cmd"]
    p = doc["parsed"]
    for key in ("serve_tokens_per_sec_paged_kernel",
                "paged_hbm_bytes_vs_slab_int8",
                "serve_greedy_match_rate_int8kv", "paged_hbm_bytes_int8",
                "serve_paged_kernel_host_ops_per_block"):
        assert key in p, key
    assert not [k for k in p if k.endswith("_error")], "a section failed"
    # the acceptance criteria, pinned on the committed artifact
    assert p["paged_hbm_bytes_vs_slab_int8"] <= 0.5
    assert p["serve_greedy_match_rate_int8kv"] == 1.0
    assert p["serve_paged_kernel_host_ops_per_block"] == 2.0
    rc, summary, err = _regress(REPO / "BENCH_r10.json",
                                REPO / "BENCH_r10.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass"
    rc, summary, _ = _regress(REPO / "BENCH_r09.json",
                              REPO / "BENCH_r10.json")
    assert rc == 0, "new kernel keys must land as new_key over r09"
    bad = dict(doc, parsed=dict(p, serve_greedy_match_rate_int8kv=0.98))
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    rc, summary, _ = _regress(REPO / "BENCH_r10.json", tmp_path / "bad.json")
    assert rc == 1
    assert "serve_greedy_match_rate_int8kv" in \
        [r["key"] for r in summary["regressions"]]


def test_bench_regress_committed_r11_gates_async_keys(tmp_path):
    """ISSUE 19 satellite: BENCH_r11 (scripts/bench_cpu_basis.py
    --async-update over r10) carries the async-block-loop keys no prior
    artifact could. Self-pass, r10 -> r11 lands them as new_key, and the
    committed values meet the acceptance bars: the inter-block gap drops
    >= 2x vs the sync sidecar basis, the async loop GAINS throughput at
    small fused K, and the streams-exact sidecar (async == sync
    bit-identity, asserted inside the bench itself) is True."""
    doc = json.loads((REPO / "BENCH_r11.json").read_text())
    assert doc["rc"] == 0 and "--async-update" in doc["cmd"]
    p = doc["parsed"]
    for key in ("serve_interblock_gap_ms", "serve_interblock_gap_ms_sync",
                "serve_tokens_per_sec_async_smallK",
                "serve_tokens_per_sec_sync_smallK",
                "serve_async_streams_exact"):
        assert key in p, key
    assert not [k for k in p if k.endswith("_error")], "a section failed"
    # the acceptance criteria, pinned on the committed artifact
    assert p["serve_async_streams_exact"] is True
    assert p["serve_interblock_gap_ms_sync"] > 0.0
    assert p["serve_interblock_gap_ms"] <= \
        0.5 * p["serve_interblock_gap_ms_sync"], \
        "ISSUE 19 bar: gap must drop >= 2x vs sync"
    assert p["serve_tokens_per_sec_async_smallK"] > \
        p["serve_tokens_per_sec_sync_smallK"]
    rc, summary, err = _regress(REPO / "BENCH_r11.json",
                                REPO / "BENCH_r11.json")
    assert rc == 0, err
    assert summary["verdict"] == "pass"
    rc, summary, _ = _regress(REPO / "BENCH_r10.json",
                              REPO / "BENCH_r11.json")
    assert rc == 0, "new async keys must land as new_key over r10"
    # a regrown gap gates: the headline key is lower-better at 50% tol
    bad = dict(doc, parsed=dict(
        p, serve_interblock_gap_ms=p["serve_interblock_gap_ms_sync"]))
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    rc, summary, _ = _regress(REPO / "BENCH_r11.json", tmp_path / "bad.json")
    assert rc == 1
    assert "serve_interblock_gap_ms" in \
        [r["key"] for r in summary["regressions"]]


def test_bench_regress_async_direction_rules(tmp_path):
    """Direction-of-goodness for the async-loop keys: a RISING inter-block
    gap regresses (lower-better, 50% tolerance — the committed value is
    ~0, so any real regrowth trips it), and FALLING small-K throughput
    regresses beyond the usual 10%."""
    keys = ["serve_interblock_gap_ms", "serve_tokens_per_sec_async_smallK"]
    base = {"headline_keys": keys, "serve_interblock_gap_ms": 1.0,
            "serve_tokens_per_sec_async_smallK": 250.0}
    gap = {"headline_keys": keys, "serve_interblock_gap_ms": 40.0,
           "serve_tokens_per_sec_async_smallK": 250.0}
    slow = {"headline_keys": keys, "serve_interblock_gap_ms": 1.0,
            "serve_tokens_per_sec_async_smallK": 180.0}
    better = {"headline_keys": keys, "serve_interblock_gap_ms": 0.1,
              "serve_tokens_per_sec_async_smallK": 300.0}
    for name, doc in (("base", base), ("gap", gap), ("slow", slow),
                      ("better", better)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "gap.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == "serve_interblock_gap_ms"
    assert summary["regressions"][0]["direction"] == "lower"
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "slow.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == \
        "serve_tokens_per_sec_async_smallK"
    assert summary["regressions"][0]["direction"] == "higher"
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "better.json")
    assert rc == 0 and summary["counts"]["improved"] == 2
    # the zero-baseline absolute floor: the committed gap is EXACTLY 0.0
    # (by construction), where a relative tolerance can never trip — the
    # rule's abs_tol still gates any real regrowth, while sub-floor
    # wall-clock jitter stays ok
    zero = {"headline_keys": keys, "serve_interblock_gap_ms": 0.0,
            "serve_tokens_per_sec_async_smallK": 250.0}
    regrown = dict(zero, serve_interblock_gap_ms=40.0)
    jitter = dict(zero, serve_interblock_gap_ms=0.5)
    for name, doc in (("zero", zero), ("regrown", regrown),
                      ("jitter", jitter)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "zero.json",
                              tmp_path / "regrown.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == "serve_interblock_gap_ms"
    rc, summary, _ = _regress(tmp_path / "zero.json",
                              tmp_path / "jitter.json")
    assert rc == 0, "sub-floor jitter off a zero baseline must not gate"


def test_bench_regress_autoscale_direction_rules(tmp_path):
    """Direction-of-goodness for the autoscale keys: a FALLING
    goodput-per-capacity ratio or a RISING time-to-ready regresses; the
    reverse improves."""
    keys = ["serve_goodput_autoscale_vs_fixed",
            "serve_scaleup_time_to_ready_blocks"]
    base = {"headline_keys": keys, "serve_goodput_autoscale_vs_fixed": 1.25,
            "serve_scaleup_time_to_ready_blocks": 2.0}
    worse = {"headline_keys": keys, "serve_goodput_autoscale_vs_fixed": 0.9,
             "serve_scaleup_time_to_ready_blocks": 2.0}
    slow = {"headline_keys": keys, "serve_goodput_autoscale_vs_fixed": 1.25,
            "serve_scaleup_time_to_ready_blocks": 4.0}
    better = {"headline_keys": keys, "serve_goodput_autoscale_vs_fixed": 1.5,
              "serve_scaleup_time_to_ready_blocks": 1.0}
    for name, doc in (("base", base), ("worse", worse), ("slow", slow),
                      ("better", better)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "worse.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == \
        "serve_goodput_autoscale_vs_fixed"
    assert summary["regressions"][0]["direction"] == "higher"
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "slow.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == \
        "serve_scaleup_time_to_ready_blocks"
    assert summary["regressions"][0]["direction"] == "lower"
    rc, summary, _ = _regress(tmp_path / "base.json", tmp_path / "better.json")
    assert rc == 0 and summary["counts"]["improved"] == 2


def test_bench_regress_structured_direction_rules(tmp_path):
    """Direction-of-goodness for the structured-decoding keys: the parse
    rate is a zero-tolerance correctness gate (ANY drop from 1.0
    regresses), a falling structured-vs-freeform ITL ratio regresses
    beyond its 10% tolerance, and the one-time grammar compile cost is
    lower-better with a wide host-noise tolerance."""
    keys = ["serve_structured_parse_rate",
            "serve_itl_p50_ms_structured_vs_freeform", "grammar_compile_ms"]
    base = {"headline_keys": keys, "serve_structured_parse_rate": 1.0,
            "serve_itl_p50_ms_structured_vs_freeform": 0.98,
            "grammar_compile_ms": 400.0}
    unparsed = dict(base, serve_structured_parse_rate=0.99)
    stalled = dict(base, serve_itl_p50_ms_structured_vs_freeform=0.7)
    better = {"headline_keys": keys, "serve_structured_parse_rate": 1.0,
              "serve_itl_p50_ms_structured_vs_freeform": 1.02,
              "grammar_compile_ms": 300.0}
    for name, doc in (("base", base), ("unparsed", unparsed),
                      ("stalled", stalled), ("better", better)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "unparsed.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == "serve_structured_parse_rate"
    assert summary["regressions"][0]["direction"] == "higher"
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "stalled.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == \
        "serve_itl_p50_ms_structured_vs_freeform"
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "better.json")
    assert rc == 0 and summary["counts"].get("regressed", 0) == 0


def test_bench_regress_disagg_direction_rules(tmp_path):
    """Direction-of-goodness for the disagg keys: a RISING decode-clock
    p99 or stall beyond tolerance regresses; falling improves."""
    keys = ["serve_itl_p99_ms_disagg",
            "serve_decode_stall_ms_longprompt_disagg"]
    base = {"headline_keys": keys, "serve_itl_p99_ms_disagg": 6.4,
            "serve_decode_stall_ms_longprompt_disagg": 1.0}
    worse = {"headline_keys": keys, "serve_itl_p99_ms_disagg": 9.0,
             "serve_decode_stall_ms_longprompt_disagg": 1.0}
    better = {"headline_keys": keys, "serve_itl_p99_ms_disagg": 5.0,
              "serve_decode_stall_ms_longprompt_disagg": 0.2}
    for name, doc in (("base", base), ("worse", worse), ("better", better)):
        (tmp_path / f"{name}.json").write_text(json.dumps(doc))
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "worse.json")
    assert rc == 1
    assert summary["regressions"][0]["key"] == "serve_itl_p99_ms_disagg"
    assert summary["regressions"][0]["direction"] == "lower"
    rc, summary, _ = _regress(tmp_path / "base.json",
                              tmp_path / "better.json")
    assert rc == 0 and summary["counts"]["improved"] == 2
