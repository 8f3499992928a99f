"""SLO-driven autoscaling control plane (ISSUE 12 tentpole gates).

The acceptance surfaces:

* the ELASTICITY ORACLE — an autoscaled fleet (min 1, growing/shrinking
  live under the policy) serves token streams BIT-IDENTICAL to a fixed
  max-provisioned fleet and to a bare ServeEngine, greedy + sampled: the
  per-request rng contract (token t of request r draws
  ``fold_in(fold_in(base, r), t)``) makes streams placement-independent,
  so capacity changes are invisible in the tokens;
* DETERMINISM — a (trace, policy, seed) triple replays to the identical
  scale-event sequence (every stock signal is a virtual-block-clock
  quantity), chaos plans included;
* PARK/UNPARK — scale-down drains through the PR 7 machinery (zero token
  loss), parks a snapshot, and a later scale-up restores WARM from it via
  ``ServeEngine.from_snapshot`` — round trip bit-identical;
* CHAOS — a replica crash landing mid-scale-up (the seeded plan can only
  fire once the fleet has >= 2 live replicas, i.e. after a scale-up)
  leaves streams equal to the no-fault oracle and drains allocators to 0;
* role pools on a DisaggRouter scale INDEPENDENTLY off their own signals.

Tier-1 cost discipline: the shared tiny 2-layer module-scoped stack, K=4,
short budgets; the multi-LoRA/tier drain scenario builds its own lm once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    AutoscalePolicy,
    Autoscaler,
    CausalLM,
    DisaggRouter,
    FaultPlan,
    Router,
    Sampler,
    ServeEngine,
    run_router_trace,
)
from neuronx_distributed_tpu.inference.replay import (
    synthetic_trace,
    synthetic_trace_stream,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.observability import (
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
PAGE = 4


@pytest.fixture(scope="module")
def stack():
    """(config, params, contiguous lm, paged lm) over ONE weight set."""
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm_c = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3).compile()
    lm_p = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE).compile()
    return cfg, params, lm_c, lm_p


@pytest.fixture(scope="module")
def lora_stack(stack):
    """Paged + multi-LoRA lm (2 adapter slots past identity) sharing the
    module's weight set — built once, only if the drain scenario runs."""
    cfg, params, _lm_c, _lm_p = stack
    from neuronx_distributed_tpu.lora import LoraConfig, init_lora

    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                  max_batch=3, page_size=PAGE, lora_rank=4,
                  lora_slots=3).compile()
    acfg = LoraConfig(r=4)
    adapters = {}
    for i in range(2):
        ad = init_lora(params, acfg, jax.random.key(100 + i))
        adapters[f"a{i}"] = {
            k: {"lora_a": v["lora_a"],
                "lora_b": 0.05 * jax.random.normal(
                    jax.random.fold_in(jax.random.key(200 + i), j),
                    v["lora_b"].shape, jnp.float32)}
            for j, (k, v) in enumerate(sorted(ad.items()))}
    return lm, adapters, acfg


def _streams(obj):
    return {c.request_id: c.tokens.tolist() for c in obj.completed}


def _two_burst(seed_a=1, seed_b=2, gap=40, n=6, max_new=8):
    """Burst at block 0, idle valley, burst at ``gap`` — the scale-up /
    park / warm-unpark workload."""
    tr = synthetic_trace(n, 128, prompt_lens=(8,), max_new_tokens=max_new,
                         mean_interarrival_blocks=0.2, seed=seed_a)
    late = synthetic_trace(n, 128, prompt_lens=(8,), max_new_tokens=max_new,
                           mean_interarrival_blocks=0.2, seed=seed_b)
    for item in late:
        item["arrival_block"] += gap
    return tr + late


def _policy(**kw):
    base = dict(min_replicas=1, max_replicas=3, backlog_high_blocks=0.5,
                up_patience_blocks=1, down_utilization=0.5,
                down_patience_blocks=4, cooldown_blocks=2)
    base.update(kw)
    return AutoscalePolicy(**base)


def _submit_all(router, trace):
    for item in trace:
        router.submit(item["prompt"], item["max_new_tokens"],
                      arrival_block=item.get("arrival_block", 0),
                      sampler=item.get("sampler"))


# ------------------------------------------------ the elasticity oracle

def test_autoscaled_streams_bit_identical_to_fixed_fleet(stack):
    """Acceptance: greedy AND sampled streams from an elastic 1->3 fleet
    equal the fixed N=3 fleet's and the bare engine's, fused x paged and
    stepwise x contiguous — capacity changes move placement, never
    tokens. At least one scale-up must actually fire (the trace bursts
    past one replica's capacity)."""
    cfg, params, lm_c, lm_p = stack
    trace = synthetic_trace(8, 128, prompt_lens=(8,), max_new_tokens=8,
                            mean_interarrival_blocks=0.2, seed=1)
    # a sampled request rides along: scale events must not disturb the
    # per-request key streams
    trace[3]["sampler"] = Sampler(temperature=1.1)
    for lm, fused in ((lm_p, True), (lm_c, False)):
        eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42),
                          fused=fused)
        _submit_all(eng, trace)
        eng.run()
        oracle = _streams(eng)

        fixed = Router(lm, 3, rng=jax.random.key(42), block_steps=K,
                       fused=fused)
        _submit_all(fixed, trace)
        fixed.run()
        assert _streams(fixed) == oracle

        auto = Router(lm, 1, rng=jax.random.key(42), block_steps=K,
                      fused=fused, autoscaler=Autoscaler(_policy()))
        _submit_all(auto, trace)
        auto.run()
        assert _streams(auto) == oracle, (lm.paged, fused)
        ups = [e for e in auto.autoscaler.scale_events
               if e["action"] == "up"]
        assert ups, "the burst must force at least one scale-up"
        assert len(auto.engines) > 1


def test_scale_events_replay_twice_identical(stack):
    """Determinism: the same (trace, policy, seed) triple produces the
    IDENTICAL scale-event sequence and streams on a re-run — every stock
    signal lives on the virtual block clock."""
    _cfg, _params, _lm_c, lm_p = stack

    def run_once():
        r = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K,
                   autoscaler=Autoscaler(_policy()))
        _submit_all(r, _two_burst())
        r.run()
        return r

    a, b = run_once(), run_once()
    assert a.autoscaler.scale_events == b.autoscaler.scale_events
    assert a.autoscaler.scale_events, "the workload must produce events"
    assert _streams(a) == _streams(b)


def test_async_fleet_scales_on_same_block_as_sync(stack):
    """PR 19 remainder: under ``async_loop=True`` every policy signal the
    autoscaler reads lags the in-flight block by one harvest.  ReplicaLoad
    stamps ``observed_block`` (the newest block whose effects the summary
    reflects) and the hysteresis credits the staleness toward patience, so
    the async fleet's scale events land on the SAME virtual block as the
    sync fleet's for the same trace — patience thresholds included
    (up_patience > 1 would otherwise trip one block late)."""
    _cfg, _params, _lm_c, lm_p = stack
    trace = _two_burst()

    def run_once(async_loop):
        r = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K,
                   async_loop=async_loop,
                   autoscaler=Autoscaler(_policy(up_patience_blocks=2)))
        _submit_all(r, trace)
        r.run()
        return r

    sync, pipe = run_once(False), run_once(True)
    assert sync.autoscaler.scale_events, "the workload must produce events"
    assert (pipe.autoscaler.scale_events
            == sync.autoscaler.scale_events)
    assert _streams(pipe) == _streams(sync)


# ------------------------------------------------ park -> warm unpark

def test_park_unpark_snapshot_roundtrip_bit_identity(stack):
    """Scale-down drains and PARKS a snapshot; the second burst's
    scale-up restores WARM from it (ServeEngine.from_snapshot — a fresh
    engine object at the same index). The full round trip is bit-identical
    to the fixed fleet serving the same submissions."""
    _cfg, _params, _lm_c, lm_p = stack
    trace = _two_burst()
    fixed = Router(lm_p, 3, rng=jax.random.key(42), block_steps=K)
    _submit_all(fixed, trace)
    fixed.run()

    auto = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K,
                  autoscaler=Autoscaler(_policy()))
    _submit_all(auto, trace)
    first_spawn = None

    # step manually so the pre-unpark engine object can be captured
    while auto.step_block():
        if first_spawn is None and len(auto.engines) > 1:
            first_spawn = auto.engines[1]
    assert _streams(auto) == _streams(fixed)
    evs = auto.autoscaler.scale_events
    acts = [e["action"] for e in evs]
    assert "down" in acts and "parked" in acts, acts
    warm_ups = [e for e in evs if e["action"] == "up" and e["warm"]]
    assert warm_ups, f"second burst must warm-unpark, got {evs}"
    i = warm_ups[0]["replica"]
    assert auto.stats["warm_spawns"] >= 1
    assert i in auto.snapshots          # the parked image it restored from
    assert auto.engines[i] is not first_spawn, \
        "warm unpark must rebuild the engine from the snapshot"
    # the drain lost nothing and the parked replica's allocator is empty
    assert sum(len(c.tokens) for c in auto.completed) == \
        sum(len(c.tokens) for c in fixed.completed)


# ------------------------------------------------ chaos

def test_replica_crash_during_scaleup_chaos(stack):
    """The seeded crash plan can only fire with >= 2 live replicas — i.e.
    necessarily inside a scale-up window on a min=1 fleet. Streams must
    equal the no-fault bare-engine oracle, every live allocator drains to
    0, and the whole run (scale events + crash) replays identically."""
    _cfg, _params, _lm_c, lm_p = stack
    trace = _two_burst()
    eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(42))
    _submit_all(eng, trace)
    eng.run()
    oracle = _streams(eng)

    def run_once():
        r = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K,
                   autoscaler=Autoscaler(_policy()),
                   faults=FaultPlan(replica_crash_prob=0.4,
                                    max_replica_crashes=1, seed=9),
                   record_streams=True)
        _submit_all(r, trace)
        r.run()
        return r

    a = run_once()
    assert a.stats["crashes"] == 1, "the plan must fire once"
    assert _streams(a) == oracle
    ups = [e["block"] for e in a.autoscaler.scale_events
           if e["action"] == "up"]
    assert ups
    # allocators drain to 0 on every non-dead replica
    for i, e in enumerate(a.engines):
        if not a._alive[i] or e.session.paged is None:
            continue
        if e.session.paged.prefix is not None:
            e.session.paged.prefix.drop_tiered()
            e.session.paged.prefix.evict(10 ** 6)
        assert e.session.paged.allocator.in_use() == 0, i
    b = run_once()
    assert b.autoscaler.scale_events == a.autoscaler.scale_events
    assert _streams(b) == oracle


# ------------------------------------------------ drain migrates state

def test_scale_down_drain_migrates_pinned_adapters_and_tiered_prefixes(
        lora_stack):
    """Autoscaler-initiated scale-down on a tiered multi-LoRA fleet: the
    drain catches the victim MID-CHUNKED-PREFILL of an adapter-pinned
    request (scaled to 3, then every replica holds one long cold prompt
    when utilization drops under threshold — the least-loaded victim is
    carrying real work), migrates it atomically (page rollback + pin
    released at the source, re-acquired by the destination's admission),
    and a late request re-serving a family the victim's radix held still
    streams bit-identical to the bare-engine oracle.

    This scenario is ALSO the regression pin for the adapter-namespaced
    radix (the late a0 request shares a page-aligned prefix with phase-1
    BASE-model traffic — before the namespace fix the oracle reused the
    identity-adapter prefix KV and produced wrong tokens)."""
    lm, adapters, acfg = lora_stack
    rs = np.random.RandomState(3)
    fam = [rs.randint(1, 127, (8,)).astype(np.int32) for _ in range(2)]

    def submits():
        rs2 = np.random.RandomState(5)
        out = []
        # phase 1 — base-model burst on the shared families: scales 1 -> 3
        for i in range(9):
            p = np.concatenate([fam[i % 2], rs2.randint(1, 127, (4,))
                                .astype(np.int32)])
            out.append(dict(prompt=p, max_new_tokens=8, arrival_block=0))
        # phase 2 — three COLD long adapter prompts (no shared prefix, so
        # least-loaded placement spreads one per replica) chunk-prefill
        # while fleet utilization sits under the scale-down threshold
        for i in range(3):
            out.append(dict(prompt=rs2.randint(1, 127, (24,))
                            .astype(np.int32),
                            max_new_tokens=8, adapter=f"a{i % 2}",
                            arrival_block=12))
        # phase 3 — the late a0 request on family 0 (the cross-adapter
        # prefix-poisoning regression pin), arriving post-park
        out.append(dict(prompt=np.concatenate(
            [fam[0], rs2.randint(1, 127, (4,)).astype(np.int32)]),
            max_new_tokens=8, adapter="a0", arrival_block=40))
        return out

    def fill(target):
        for n, ad in adapters.items():
            target.register_adapter(n, ad, acfg)
        for kw in submits():
            target.submit(**kw)

    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=8, prefill_chunk_tokens=4)
    fill(eng)
    eng.run()
    oracle = _streams(eng)

    auto = Router(lm, 1, rng=jax.random.key(42), block_steps=K,
                  host_tier_pages=8, prefill_chunk_tokens=4,
                  autoscaler=Autoscaler(_policy(down_patience_blocks=3,
                                                down_utilization=0.6)))
    fill(auto)
    auto.run()
    assert _streams(auto) == oracle
    evs = auto.autoscaler.scale_events
    assert any(e["action"] == "down" for e in evs), evs
    # the drain caught real work: an in-flight chunked admission was
    # unwound atomically and re-placed on a peer
    assert auto.stats["drain_migrated_requests"] >= 1
    assert sum(int(e.stats["prefill_aborts"]) for e in auto.engines) >= 1
    # a parked victim holds no adapter pins (extract released them)
    for i in auto._drained:
        pool = auto.engines[i].session.adapters
        assert not any(pool.pinned(n) for n in pool.resident)
    # the adapter work landed somewhere: fleet-wide loads happened
    assert sum(e.session.adapters.stats["loads"]
               for e in auto.engines) > 0


# ------------------------------------------------ disaggregated pools

def test_disagg_pools_scale_independently(stack):
    """On a DisaggRouter each role pool runs its own policy: the
    fresh-prompt backlog grows the PREFILL pool, mid-stream/handoff
    pressure grows the DECODE pool — events carry the role, role tables
    extend, and streams equal the single-engine oracle (the folded
    ROADMAP #13 remainder)."""
    _cfg, _params, _lm_c, lm_p = stack
    trace = _two_burst()
    eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(42))
    _submit_all(eng, trace)
    eng.run()
    oracle = _streams(eng)

    pols = {r: _policy(max_replicas=2, backlog_high_blocks=0.3,
                       down_patience_blocks=4)
            for r in ("prefill", "decode")}
    rd = DisaggRouter(lm_p, 2, prefill_replicas=1, rng=jax.random.key(42),
                      block_steps=K, autoscaler=Autoscaler(per_role=pols))
    _submit_all(rd, trace)
    rd.run()
    assert _streams(rd) == oracle
    roles_up = {e["role"] for e in rd.autoscaler.scale_events
                if e["action"] == "up"}
    assert roles_up == {"prefill", "decode"}, rd.autoscaler.scale_events
    assert len(rd.roles) == len(rd.engines) > 2
    for i, role in enumerate(rd.roles):
        assert rd.engines[i].role == role


# ------------------------------------------------ policy units

def test_policy_bounds_cooldown_and_validation(stack):
    """max_replicas caps growth, min_replicas floors scale-down, and
    same-role scale events respect the cooldown spacing; bad knob
    combinations raise."""
    _cfg, _params, _lm_c, lm_p = stack
    with pytest.raises(ValueError):
        AutoscalePolicy(min_replicas=0)
    with pytest.raises(ValueError):
        AutoscalePolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        AutoscalePolicy(backlog_high_blocks=0)
    with pytest.raises(ValueError):
        AutoscalePolicy(down_utilization=1.0)

    pol = _policy(max_replicas=2, cooldown_blocks=4)
    r = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K,
               autoscaler=Autoscaler(pol))
    # a heavy burst: without the cap this would want 3+ replicas
    _submit_all(r, synthetic_trace(10, 128, prompt_lens=(8,),
                                   max_new_tokens=8,
                                   mean_interarrival_blocks=0.1, seed=4))
    r.run()
    assert len(r.engines) <= 2
    evs = [e for e in r.autoscaler.scale_events
           if e["action"] in ("up", "down") and e["reason"] != "min_replicas"]
    blocks = [e["block"] for e in evs]
    assert all(b2 - b1 >= pol.cooldown_blocks
               for b1, b2 in zip(blocks, blocks[1:])), evs
    # never below the floor: at least min_replicas stayed live throughout
    assert len(r._live_replicas()) >= pol.min_replicas


def test_replica_load_struct_is_shared_surface(stack):
    """ISSUE 12 satellite: ONE typed ReplicaLoad struct feeds placement,
    the policy, replica_states() and the incident state card."""
    _cfg, _params, _lm_c, lm_p = stack
    from neuronx_distributed_tpu.inference import ReplicaLoad
    from neuronx_distributed_tpu.observability import default_slos

    eng = ServeEngine(lm_p, block_steps=K, rng=jax.random.key(0),
                      host_tier_pages=4,
                      slos=default_slos(target=0.9))
    load = eng.load_summary()
    assert isinstance(load, ReplicaLoad)
    assert load.role == "both" and load.free_slots == lm_p.max_batch
    assert load.backlog == 0 and load.est_ttft_blocks == 0
    assert load.pages_in_use == 0 and load.pages_free is not None
    assert load.tier_pages == 0            # tier armed, nothing spilled
    assert load.adapters_resident is None  # no LoRA pool on this lm
    assert load.slo_alerting is False
    eng.submit(np.arange(1, 9, dtype=np.int32), 12)
    eng.step_block()
    busy = eng.load_summary()
    assert busy.active_slots == 1 and busy.pages_in_use > 0
    # the engine state card nests the same struct
    assert eng.state_summary()["load"] == busy.to_dict()
    # the router card = membership state + heartbeat over the struct
    r = Router(lm_p, 2, rng=jax.random.key(0), block_steps=K)
    states = r.replica_states()
    assert [s["replica"] for s in states] == [0, 1]
    for s in states:
        assert s["state"] == "live"
        for key in ("role", "est_ttft_blocks", "free_slots", "backlog",
                    "pages_free", "tier_pages", "adapters_resident",
                    "slo_alerting"):
            assert key in s, key


def test_scale_observability_lanes_metrics_and_incident(stack, tmp_path):
    """Scale decisions are observable everywhere they should be: tracer
    ("router","scale") lane instants + replicas_active counter track
    (Chrome export validates), the serve_replicas_active gauge, and a
    schema-valid 'scale' incident bundle."""
    _cfg, _params, _lm_c, lm_p = stack
    r = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K, trace=True,
               incident_dir=str(tmp_path),
               autoscaler=Autoscaler(_policy()))
    _submit_all(r, _two_burst())
    r.run()
    evs = r.tracer.events()
    names = {ev["name"] for ev in evs if ev["lane"] == ("router", "scale")}
    assert "scale_up" in names and "replicas_active" in names, names
    assert {"scale_down", "scale_parked"} <= names, names
    doc = r.tracer.export_chrome(str(tmp_path / "trace.json"))
    validate_chrome_trace(doc)
    sample = dict(r.metrics.snapshot())["serve_replicas_active"]
    assert sample["samples"][0]["value"] >= 1
    scale_bundles = [p for p in r.incident.bundles if "_scale_" in p]
    assert scale_bundles, r.incident.bundles
    summary = validate_incident_bundle(scale_bundles[0])
    assert summary["kind"] == "scale"
    # the autoscale section rides the router report
    r2 = Router(lm_p, 1, rng=jax.random.key(42), block_steps=K,
                autoscaler=Autoscaler(_policy()))
    rep = run_router_trace(
        r2, synthetic_trace_stream(6, 128, prompt_lens=(8,),
                                   max_new_tokens=6,
                                   mean_interarrival_blocks=0.2, seed=1))
    assert rep["autoscale"]["scale_ups"] >= 1
    assert rep["autoscale"]["time_to_ready_blocks_mean"] is not None
    assert rep["replica_blocks"] > 0
