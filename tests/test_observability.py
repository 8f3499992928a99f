"""Observability-layer gates (ISSUE 6 tentpole).

Four claims, each pinned against the serving engine rather than in
isolation:

* LIFECYCLE COVERAGE — a mixed chunked-prefill + overload + fault-injection
  run exports valid Chrome trace-event JSON whose per-request lanes cover
  every lifecycle state (queued span, chunk rounds, decode tokens, shed,
  expiry, retire), checked by the schema validator the tier-1 CLI smoke
  also runs.
* STATS PARITY — the legacy ``engine.stats`` dict surface is now a view
  over MetricsRegistry counters: every pre-existing key is present and
  equals its backing counter on the same run, and the same values ride the
  Prometheus exposition.
* SINGLE SOURCE OF TRUTH — ``run_trace``'s ITL/stall percentiles (computed
  from tracer token events) equal the legacy per-completion ``token_ts``
  formula they replaced, on a reference trace.
* ZERO PROGRAM IMPACT — tracing on vs off reuses the SAME compiled
  programs (cache-key identity — instrumentation is invisible to XLA) and
  produces bit-identical token streams.

Tier-1 cost discipline: ONE module-scoped contiguous CausalLM (the sibling
suites' tiny 2-layer config, block_steps=4) serves every test; registry/
tracer units need no model at all.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
from neuronx_distributed_tpu.inference.engine import _STAT_KEYS
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.inference.faults import FaultPlan
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability import (
    BurnRule,
    FlightRecorder,
    MetricsRegistry,
    SLObjective,
    SLOMonitor,
    Tracer,
    default_slos,
    parse_prometheus,
    validate_chrome_trace,
    validate_incident_bundle,
)
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4


@pytest.fixture(scope="module")
def lm():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3).compile()


def _prompts(n, s=8, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


# ------------------------------------------------- lifecycle + trace schema

def test_mixed_run_exports_full_lifecycle_trace(lm, tmp_path):
    """The acceptance gate: chunked prefill + overload (shed + queued
    expiry) + injected dispatch faults in ONE traced run; the export loads
    as valid Chrome trace JSON and the request lanes cover every lifecycle
    state."""
    eng = ServeEngine(
        lm, block_steps=K, trace=True, prefill_chunk_tokens=4,
        max_queue=1, rng=jax.random.key(7), dispatch_retries=6,
        # seeded transient dispatch failures absorbed by retry (the seeded
        # stream + fixed schedule make the fault pattern deterministic);
        # streams stay bit-identical
        faults=FaultPlan(dispatch_fail_prob=0.4, dispatch_max_failures=1,
                         seed=3))
    short = _prompts(2, s=4, seed=3)
    long16 = _prompts(2, s=16, seed=5)
    # EDF admits the deadline'd request first: it claims a slot, chunk-
    # prefills 4 tokens/round, and its 2-block TTFT deadline dies MID-
    # PREFILL (atomic abort + expire — no first token is ever sampled)
    expiring = eng.submit(long16[0], 6, ttft_deadline_ms=2.0)
    chunked = eng.submit(long16[1], 6)       # chunked: 16 tokens, C=4
    inserted = eng.submit(short[0], 12)      # one-shot insert (4 <= C)
    waiting = eng.submit(short[1], 12)       # queued until a slot frees
    # arrived backlog == max_queue + free slots: the 5th submit is shed
    shed = eng.submit(short[0], 4)
    assert isinstance(expiring, int) and isinstance(waiting, int)
    assert not isinstance(shed, int), "5th submit must be shed"
    comps = eng.run()
    assert any(c.expired and c.request_id == expiring for c in comps)
    assert any(c.request_id == waiting and not c.expired for c in comps)
    assert eng.stats["dispatch_retries"] > 0         # faults really fired

    path = tmp_path / "serve_trace.json"
    eng.tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    summary = validate_chrome_trace(doc)
    assert summary["events"] > 50
    assert {"engine", "req"} <= set(summary["processes"])
    # per-request lanes exist for every submitted id (shed victim included)
    assert set(summary["request_lanes"]) >= {expiring, chunked, inserted,
                                             waiting, shed.request_id}
    required = {"submit", "queued", "admit", "first_token", "tok", "retire",
                "chunk_begin", "prefill_chunk", "prefill_abort", "shed",
                "expire", "decode_block", "fetch", "insert", "extend",
                "decode", "fault:dispatch", "queue_depth"}
    missing = required - summary["names"]
    assert not missing, f"lifecycle states missing from trace: {missing}"
    # the full chunked request's lane: 4 chunk rounds, retired at the end
    tl = eng.request_timeline(chunked)
    names = [e["name"] for e in tl]
    assert names[0] == "submit" and "chunk_begin" in names
    assert names.count("prefill_chunk") == 16 // 4
    assert names[-1] == "retire"
    ts = [e["ts_ms"] for e in tl]          # timeline is time-ordered
    assert ts == sorted(ts)
    # the expiring request's lane ends in expire, with NO first token
    names_exp = [e["name"] for e in eng.request_timeline(expiring)]
    assert names_exp[-1] == "expire" and "first_token" not in names_exp
    assert "prefill_abort" in names_exp


def test_request_timeline_empty_when_tracing_off(lm):
    eng = ServeEngine(lm, block_steps=K)
    eng.submit(_prompts(1)[0], 4)
    eng.run()
    assert eng.request_timeline(0) == []
    assert eng.tracer.events() == []


# ------------------------------------------------------------- stats parity

def test_stats_parity_with_metrics_registry(lm):
    """Satellite gate: every pre-existing ``engine.stats`` key still exists
    and carries the value of its backing registry counter on an unchanged
    reference trace — one store, two read surfaces."""
    trace = synthetic_trace(5, 128, prompt_lens=(6, 8), max_new_tokens=6,
                            mean_interarrival_blocks=0.7, seed=3)
    eng = ServeEngine(lm, block_steps=K, trace=True)
    report = run_trace(eng, trace)
    assert report["requests_completed"] == 5
    # the full legacy key set survives, dict-style access included
    assert set(_STAT_KEYS) <= set(eng.stats.keys())
    legacy = dict(eng.stats)
    assert legacy["inserted_requests"] == 5
    assert legacy["program_calls"] == legacy["host_fetches"] \
        == legacy["decode_blocks"]
    for k in _STAT_KEYS:
        assert eng.stats[k] == eng.metrics.counter("serve_" + k).value, k
    # ad-hoc keys keep working through the view (setdefault path)
    eng.stats.setdefault("ad_hoc", 0)
    eng.stats["ad_hoc"] += 3
    assert eng.stats["ad_hoc"] == 3 \
        and eng.metrics.counter("serve_ad_hoc").value == 3
    # and the exposition carries the same numbers
    fams = parse_prometheus(eng.metrics.to_prometheus())
    assert fams["serve_inserted_requests"]["samples"][
        ("serve_inserted_requests", ())] == 5.0
    assert "serve_dispatch_ms" in fams and "serve_ttft_ms" in fams
    assert "compile_ms" in fams     # compile-vs-execute split present


# ------------------------------------- run_trace percentiles: old == new

def test_itl_percentiles_match_legacy_token_ts_path(lm):
    """The run_trace fix's parity gate: ITL/stall percentiles computed from
    tracer token events must equal the legacy per-completion ``token_ts``
    formula (np.diff > 0 filter) they replaced, on a reference trace."""
    trace = synthetic_trace(6, 128, prompt_lens=(6, 8, 12),
                            max_new_tokens=8, mean_interarrival_blocks=0.5,
                            seed=11)
    eng = ServeEngine(lm, block_steps=K, trace=True)
    report = run_trace(eng, trace)
    completions = eng.completed
    gaps = []
    legacy_per_req = {}
    for c in completions:
        g = (np.diff(c.token_ts) * 1e3
             if c.token_ts is not None and len(c.token_ts) > 1
             else np.zeros((0,)))
        g = g[g > 0.0]
        gaps.extend(g.tolist())
        legacy_per_req[c.request_id] = (
            round(float(g.max()), 2) if g.size else 0.0)
    assert gaps, "reference trace produced no delivery gaps"
    assert report["itl_p50_ms"] == pytest.approx(
        round(float(np.percentile(gaps, 50)), 3))
    assert report["itl_p99_ms"] == pytest.approx(
        round(float(np.percentile(gaps, 99)), 3))
    assert report["max_itl_gap_ms"] == pytest.approx(
        round(float(np.max(gaps)), 2))
    for pr in report["per_request"]:
        assert pr["max_itl_gap_ms"] == pytest.approx(
            legacy_per_req[pr["request_id"]]), pr["request_id"]


# ---------------------------------------- tracing cannot touch programs

def test_programs_identical_and_streams_bitwise_traced_vs_untraced(lm):
    """Tracing on vs off: the fused session program comes from the SAME
    cache entry (key set unchanged, executable identity — nothing about
    instrumentation reaches XLA) and token streams are bit-identical."""
    p = _prompts(3, seed=9)
    submits = [dict(prompt=p[0], max_new_tokens=8),
               dict(prompt=p[1], max_new_tokens=6, arrival_block=1),
               dict(prompt=p[2], max_new_tokens=7, arrival_block=2)]
    keys_before = set(lm._session_fused)
    compile_before = dict(lm.compile_ms)
    results = {}
    for trace in (True, False):
        eng = ServeEngine(lm, block_steps=K, trace=trace,
                          rng=jax.random.key(42))
        ids = [eng.submit(**kw) for kw in submits]
        comps = {c.request_id: c for c in eng.run()}
        results[trace] = {r: comps[r].tokens.tolist() for r in ids}
    assert results[True] == results[False]
    # no new program compiled for either mode, byte-identical by identity:
    # both engines hit the one cached executable (or, had none existed yet,
    # exactly one was compiled and then shared)
    assert set(lm._session_fused) == keys_before or \
        len(lm._session_fused) == len(keys_before) + 1
    assert len({id(v) for v in lm._session_fused.values()}) \
        == len(lm._session_fused)
    # compile timings recorded once per signature, never re-triggered by
    # toggling tracing
    for sig, ms in compile_before.items():
        assert lm.compile_ms[sig] == ms, sig


# --------------------------------------------------- registry / tracer units

def test_metrics_registry_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests").inc(41)
    reg.counter("reqs_total").inc()
    g = reg.gauge("depth", help="queue depth")
    g.set(7)
    g.set(3)
    h = reg.histogram("lat_ms", lo=1.0, growth=2.0, n_buckets=8)
    for v in (0.5, 1.5, 3.0, 100.0, 1e9):
        h.observe(v)
    labeled = reg.counter("dispatch_total", kind="insert")
    labeled.inc(5)
    text = reg.to_prometheus()
    fams = parse_prometheus(text)
    assert fams["reqs_total"]["type"] == "counter"
    assert fams["reqs_total"]["samples"][("reqs_total", ())] == 42.0
    # gauge carries the last value AND the peak
    assert fams["depth"]["samples"][("depth", ())] == 3.0
    assert fams["depth"]["samples"][("depth_max", ())] == 7.0
    assert fams["dispatch_total"]["samples"][
        ("dispatch_total", (("kind", "insert"),))] == 5.0
    # histogram: cumulative buckets end at +Inf == count, sum preserved
    hs = fams["lat_ms"]["samples"]
    assert hs[("lat_ms_count", ())] == 5.0
    assert hs[("lat_ms_sum", ())] == pytest.approx(1e9 + 105.0)
    inf_key = [k for k in hs if k[0] == "lat_ms_bucket"
               and ("le", "+Inf") in k[1]]
    assert len(inf_key) == 1 and hs[inf_key[0]] == 5.0
    # quantile edges are honest overestimates (log-bucket upper edge)
    assert h.percentile(50) >= 3.0
    # one name cannot be two kinds
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("reqs_total")


def test_tracer_ring_buffer_and_disabled_cost():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}", ("engine", "t"))
    assert len(tr.events()) == 8 and tr.dropped == 12
    doc = tr.export_chrome()
    assert doc["otherData"]["dropped_events"] == 12
    # ISSUE 9 satellite: the drop count is STAMPED into the event stream
    # (a viewer that keeps only traceEvents still learns the window is
    # partial) and the schema validator surfaces it in its summary
    meta_drop = [ev for ev in doc["traceEvents"]
                 if ev["ph"] == "M" and ev["name"] == "trace_dropped_events"]
    assert len(meta_drop) == 1 and meta_drop[0]["args"]["dropped"] == 12
    summary = validate_chrome_trace(doc, require_request_lanes=False)
    assert summary["dropped_events"] == 12
    # a full buffer reports zero everywhere
    full = Tracer(capacity=64)
    full.instant("x", ("engine", "t"))
    assert validate_chrome_trace(
        full.export_chrome(),
        require_request_lanes=False)["dropped_events"] == 0
    off = Tracer(enabled=False)
    off.instant("x", ("engine", "t"))
    with off.span("s", ("engine", "t")):
        pass
    assert off.events() == [] and off.dropped == 0
    # a span whose body raises still records, marked with the error
    tr2 = Tracer()
    with pytest.raises(RuntimeError):
        with tr2.span("boom", ("engine", "t")):
            raise RuntimeError("x")
    ev = tr2.events("boom")[0]
    assert ev["args"]["error"] == "RuntimeError"


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"foo": 1})
    good = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "engine"}},
        {"name": "a", "ph": "i", "pid": 1, "tid": 0, "ts": 2.0},
    ]}
    validate_chrome_trace(good, require_request_lanes=False)
    bad_order = {"traceEvents": good["traceEvents"] + [
        {"name": "b", "ph": "i", "pid": 1, "tid": 0, "ts": 1.0}]}
    with pytest.raises(ValueError, match="out of order"):
        validate_chrome_trace(bad_order, require_request_lanes=False)
    bad_dur = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad_dur, require_request_lanes=False)
    with pytest.raises(ValueError, match="request lanes"):
        validate_chrome_trace(good)


# ---------------------------------------- prometheus conformance (ISSUE 9)

def test_prometheus_label_escaping_round_trips():
    """Conformance satellite: label values containing quotes, backslashes,
    newlines and closing braces must survive exposition -> parse intact
    (the spec escapes them; the old writer emitted them raw, producing
    lines no conforming scraper could read)."""
    reg = MetricsRegistry()
    hairy = 'sig="insert{rows=1}"\\bucket\n8'
    reg.counter("compile_events_total", program=hairy).inc(3)
    reg.gauge("g", kind='q"}x').set(7)
    h = reg.histogram("h_ms", lo=1.0, n_buckets=4, label='a"b')
    h.observe(2.0)
    text = reg.to_prometheus()
    fams = parse_prometheus(text)
    assert fams["compile_events_total"]["samples"][
        ("compile_events_total", (("program", hairy),))] == 3.0
    assert fams["g"]["samples"][("g", (("kind", 'q"}x'),))] == 7.0
    labeled = [k for k in fams["h_ms"]["samples"]
               if k[0] == "h_ms_count"]
    assert labeled and dict(labeled[0][1])["label"] == 'a"b'
    # and a second exposition of the parsed values is identical (stable)
    assert reg.to_prometheus() == text


def test_histogram_count_le_is_conservative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", lo=1.0, growth=2.0, n_buckets=6)
    for v in (0.5, 1.0, 3.0, 7.0, 9.0, 100.0):
        h.observe(v)
    # edges: 1, 2, 4, 8, 16, 32, +Inf
    assert h.count_le(8.0) == 4          # 0.5, 1.0, 3.0, 7.0
    assert h.count_le(1.0) == 2
    # 9.0 sits in (8, 16]: not provably <= 10, so excluded (conservative)
    assert h.count_le(10.0) == 4
    # a finite bound cannot vouch for the +Inf overflow bucket (100.0)...
    assert h.count_le(1e9) == h.count - 1
    # ... but an infinite one covers everything
    assert h.count_le(float("inf")) == h.count


# ------------------------------------------------- SLO burn-rate monitor

def test_slo_monitor_multiwindow_burn_alerts():
    """Unit gate on the virtual clock: a latency objective whose error
    rate jumps from 0 to 100% must alert once both windows see the burn,
    de-latch when the short window recovers, and re-alert on a second
    violation — with the alert counter and tracer instants in agreement."""
    reg = MetricsRegistry()
    tr = Tracer()
    h = reg.histogram("lat_ms", lo=1.0, growth=2.0, n_buckets=10)
    mon = SLOMonitor(
        reg, [SLObjective(name="lat", target=0.9, metric="lat_ms",
                          objective_ms=8.0)],
        rules=[BurnRule(long_blocks=8, short_blocks=2, factor=2.0)],
        tracer=tr, lane="engine")
    block = 0
    for _ in range(4):                      # healthy: all good
        h.observe(2.0)
        assert mon.observe_block(block) == []
        block += 1
    fired_at = None
    for _ in range(6):                      # incident: all bad
        h.observe(100.0)
        fired = mon.observe_block(block)
        if fired and fired_at is None:
            fired_at = block
            assert fired[0]["slo"] == "lat"
            assert fired[0]["burn_short"] > 2.0
        block += 1
    assert fired_at is not None, "burn never alerted"
    assert len(mon.alerts) == 1             # latched: one alert per episode
    st = mon.status()["lat"]
    assert st["compliance"] < 0.9
    assert any(r and r["alerting"] for r in st["rules"].values())
    for _ in range(6):                      # recovery: all good again
        h.observe(2.0)
        mon.observe_block(block)
        block += 1
    assert not any(r and r["alerting"]
                   for r in mon.status()["lat"]["rules"].values())
    for _ in range(4):                      # second incident: fresh alert
        h.observe(100.0)
        mon.observe_block(block)
        block += 1
    assert len(mon.alerts) == 2
    assert len(tr.events("slo_alert")) == 2
    assert reg.counter("serve_slo_alerts_total", slo="lat",
                       rule="8b/2b x2").value == 2


def test_slo_error_ratio_objective():
    reg = MetricsRegistry()
    bad = reg.counter("serve_expired")
    total = reg.counter("serve_inserted_requests")
    mon = SLOMonitor(
        reg, [SLObjective(name="completion", target=0.9, kind="error_ratio",
                          bad="serve_expired",
                          total="serve_inserted_requests")],
        rules=[BurnRule(4, 2, 1.5)])
    for b in range(4):
        total.inc(5)
        assert mon.observe_block(b) == []
    total.inc(5)
    bad.inc(4)                              # 80% errors vs 10% budget
    fired = mon.observe_block(4)
    total.inc(5)
    bad.inc(4)
    fired = fired or mon.observe_block(5)
    assert fired and fired[0]["slo"] == "completion"
    with pytest.raises(ValueError, match="error_ratio"):
        SLObjective(name="x", target=0.9, kind="error_ratio")
    with pytest.raises(ValueError, match="target"):
        SLObjective(name="x", target=1.5, metric="m", objective_ms=1.0)
    assert [o.name for o in default_slos(ttft_ms=5.0)] == [
        "ttft", "completion"]


def test_engine_slo_wiring_and_report_status(lm):
    """Integration: an engine built with objectives evaluates them per
    block — an impossible objective alerts, a trivial one stays quiet, and
    both report through slo_status()."""
    trace_kw = dict(block_steps=K, trace=True, rng=jax.random.key(11))
    eng = ServeEngine(
        lm, slos=[SLObjective(name="tight", target=0.9,
                              metric="serve_ttft_ms", objective_ms=1e-6),
                  SLObjective(name="loose", target=0.9,
                              metric="serve_ttft_ms", objective_ms=1e9)],
        **trace_kw)
    for i, p in enumerate(_prompts(4, seed=13)):
        eng.submit(p, 6, arrival_block=i)
    eng.run()
    st = eng.slo_status()
    assert st["tight"]["compliance"] == 0.0 and st["tight"]["alerts"] >= 1
    assert st["loose"]["compliance"] == 1.0 and st["loose"]["alerts"] == 0
    assert eng.tracer.events("slo_alert")
    # no objectives -> no monitor, no status (the zero-cost default)
    bare = ServeEngine(lm, block_steps=K)
    assert bare._slo is None and bare.slo_status() is None


# ------------------------------------------------- incident flight recorder

def test_flight_recorder_bounds_and_schema(tmp_path):
    tr = Tracer()
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    for b in range(30):
        tr.instant("tok", ("req", 1), block=b)
    rec = FlightRecorder(str(tmp_path), tracer=tr, metrics=reg,
                         window_blocks=5, max_events=4, max_bundles=2,
                         min_gap_blocks=8)
    p1 = rec.trigger("manual", 20, details={"x": 1},
                     state={"blocks": 20})
    assert p1 is not None
    s = validate_incident_bundle(p1)
    assert s["kind"] == "manual" and s["has_metrics"]
    assert s["events"] <= 4 and s["truncated"]
    # every sliced event sits inside the declared window
    doc = json.loads(open(p1).read())
    assert all(20 - 5 <= ev["block"] <= 20
               for ev in doc["trace"]["events"] if ev["block"] is not None)
    # rate limit: same kind within min_gap is suppressed
    assert rec.trigger("manual", 24) is None and rec.suppressed == 1
    # bundle budget: the cap holds across kinds
    assert rec.trigger("page_corruption", 29) is not None
    assert rec.trigger("deadline_miss_burst", 29) is None
    assert len(rec.bundles) == 2
    with pytest.raises(ValueError, match="unknown incident kind"):
        rec.trigger("nope", 1)
    # schema gate rejects malformed bundles
    with pytest.raises(ValueError, match="schema_version"):
        validate_incident_bundle({"kind": "manual"})
    bad = json.loads(open(p1).read())
    bad["trace"]["events"].append({"name": "late", "ph": "i",
                                   "lane": ["req", 1], "block": 99})
    with pytest.raises(ValueError, match="postdates"):
        validate_incident_bundle(bad)


def test_deadline_burst_dumps_incident_bundle(lm, tmp_path):
    """Integration: an overload that expires a burst of deadlines trips
    the engine's burst detector exactly once (rate-limited), and the
    bundle carries the trace slice, the state card and the metrics
    snapshot the diagnosis needs."""
    eng = ServeEngine(lm, block_steps=K, trace=True,
                      rng=jax.random.key(3),
                      incident_dir=str(tmp_path),
                      incident_burst_threshold=3, incident_burst_window=8)
    # 3 slots, 6 arrivals: the queued half's 2-block TTFT budget dies
    # before the first cohort (10 tokens = 3 blocks) frees a slot
    for p in _prompts(6, s=8, seed=9):
        eng.submit(p, 10, ttft_deadline_ms=2.0)
    comps = eng.run(max_blocks=300)
    assert sum(1 for c in comps if c.expired) >= 3
    bundles = [b for b in eng.incident.bundles
               if "deadline_miss_burst" in b]
    assert len(bundles) == 1
    s = validate_incident_bundle(bundles[0])
    assert s["kind"] == "deadline_miss_burst"
    assert "expire" in s["names"]           # the slice shows the misses
    doc = json.loads(open(bundles[0]).read())
    assert doc["details"]["misses_in_window"] >= 3
    assert doc["state"]["engine"] == "engine"
    assert doc["state"]["stats"]["expired"] >= 3
    assert "serve_ttft_ms" in doc["metrics"]


def test_engine_trace_drop_counter(lm):
    """Satellite: ring-buffer drops surface as the trace_dropped_events
    counter (and run_trace's report) instead of dying sidecar-only."""
    tr = Tracer(capacity=32)
    eng = ServeEngine(lm, block_steps=K, tracer=tr)
    for i, p in enumerate(_prompts(3, seed=17)):
        eng.submit(p, 8, arrival_block=i)
    eng.run()
    assert tr.dropped > 0
    assert eng.metrics.counter("trace_dropped_events").value == tr.dropped


# ---------------------------------------------- multi-LoRA lanes (ISSUE 10)

def test_multilora_observability_lanes_and_attribution():
    """ISSUE 10 observability satellite, pinned on one tiny lora engine:

    * pool lifecycle instants (``adapter:load/pin/evict``) land on the
      ``("cache", "adapter")`` lane and the ``adapter_pool_pages`` counter
      track rides the schema-valid Chrome export;
    * ``request_timeline`` shows the ``adapter_load`` mark inside the
      admission (between the queued span and first_token);
    * an injected adapter-load fault becomes an ``adapter_load`` phase in
      the attribution — and the phase-sum == e2e invariant (asserted
      inside ``request_attribution``) stays exact with the new phase.
    """
    from neuronx_distributed_tpu.inference.faults import FaultPlan
    from neuronx_distributed_tpu.lora import LoraConfig, init_lora
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm_l = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,),
                    max_batch=2, lora_rank=2, lora_slots=2).compile()
    acfg = LoraConfig(r=2, lora_alpha=4.0)

    def mk(i):
        ad = init_lora(params, acfg, jax.random.key(30 + i))
        return {k: {"lora_a": v["lora_a"],
                    "lora_b": 0.05 * jax.random.normal(
                        jax.random.fold_in(jax.random.key(40 + i), j),
                        v["lora_b"].shape, jnp.float32)}
                for j, (k, v) in enumerate(sorted(ad.items()))}

    adapters = {f"a{i}": mk(i) for i in range(2)}
    eng = ServeEngine(lm_l, block_steps=K, trace=True,
                      rng=jax.random.key(42))
    for n, ad in adapters.items():
        eng.register_adapter(n, ad, acfg)
    p = _prompts(2, seed=21)
    r0 = eng.submit(p[0], 4, adapter="a0")
    # a1 arrives after a0 retires: its load must EVICT a0 (1 usable slot)
    r1 = eng.submit(p[1], 4, adapter="a1", arrival_block=6)
    eng.run()
    names = {ev["name"] for ev in eng.tracer.events()
             if ev["lane"] == ("cache", "adapter")}
    assert {"adapter:load", "adapter:pin", "adapter:evict"} <= names
    counters = {ev["name"] for ev in eng.tracer.events() if ev["ph"] == "C"}
    assert "adapter_pool_pages" in counters
    # Chrome export stays schema-valid with the new lanes
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json", mode="r+") as f:
        eng.tracer.export_chrome(f.name)
        summary = validate_chrome_trace(json.load(open(f.name)))
    assert summary["events"] > 0
    # request_timeline: the adapter-load mark sits inside the admission
    tl = [e["name"] for e in eng.request_timeline(r1)]
    assert "adapter_load" in tl
    assert tl.index("adapter_load") < tl.index("first_token")
    # registry surface
    assert eng.metrics.gauge("serve_adapter_slots_in_use").value == 1
    assert eng.session.adapters.stats["evictions"] == 1

    # injected load fault -> adapter_load phase, phase sum stays exact
    # (seed 8's first two adapter draws are 'fail' at p=0.3)
    eng_f = ServeEngine(lm_l, block_steps=K, trace=True,
                        rng=jax.random.key(42),
                        faults=FaultPlan(seed=8, adapter_load_fail_prob=0.3))
    for n, ad in adapters.items():
        eng_f.register_adapter(n, ad, acfg)
    rf = eng_f.submit(p[0], 4, adapter="a0")
    eng_f.run()
    assert eng_f.stats["adapter_load_retries"] >= 1
    att = eng_f.request_attribution(rf)   # internal assert: sum == e2e
    assert att["phases_blocks"].get("adapter_load", 0) >= 1
    assert att["annotations"]["adapter_defers"] >= 1
    assert att["annotations"]["adapter_loads"] == 1
    assert att["terminal"] == "retire"
