"""Virtual-clock replay driver: a synthetic load generator and the serving
reports, one layer ABOVE the engine, the router and the disaggregated fleet.

``synthetic_trace[_stream]`` draws a seeded arrival trace (prompt lengths,
arrival blocks, deadlines, tenants, adapters, grammars, cancels);
``run_trace`` / ``run_router_trace`` / ``run_disagg_trace`` submit one to a
:class:`ServeEngine` / :class:`Router` / :class:`DisaggRouter`, drive it to
completion and return the report ``examples/inference/runner.py serve``,
``scripts/soak.py``, ``chip_smoke.py`` and the tests read. The report reaches
into every layer below it (page pool, host tier, adapter pool, tracer), which
is why it lives here: this module imports ``engine``, ``router``, ``disagg``
and ``observability``; none of them imports it, and the package's
``__init__`` loads it only when one of its names is asked for. The chip
benchmark (``benchmark/``) has its own wall-clock driver and traffic
generator and uses none of this.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from neuronx_distributed_tpu.inference.disagg import DisaggRouter
from neuronx_distributed_tpu.inference.engine import (
    Completion,
    Rejected,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.router import Router
from neuronx_distributed_tpu.observability.tracer import (
    Tracer,
    interblock_gaps,
)


def synthetic_trace_stream(num_requests: int, vocab_size: int, *,
                           prompt_lens=(8, 16), max_new_tokens: int = 16,
                           mean_interarrival_blocks: float = 0.5,
                           eos_token_id: Optional[int] = None,
                           shared_prefix_len: int = 0,
                           prefix_families: int = 1,
                           long_prompt_frac: float = 0.0,
                           long_prompt_len: int = 0,
                           ttft_deadline_ms: Optional[float] = None,
                           deadline_ms: Optional[float] = None,
                           tenants: int = 0,
                           tenant_skew: float = 1.0,
                           adapters: int = 0,
                           adapter_skew: float = 1.0,
                           grammar_frac: float = 0.0,
                           grammars: Sequence[str] = (),
                           diurnal: float = 0.0,
                           diurnal_period_blocks: int = 64,
                           burst_every: int = 0,
                           burst_mult: float = 4.0,
                           seed: int = 0) -> Iterator[dict]:
    """STREAMED deterministic synthetic arrival trace (virtual time in
    blocks): a generator yielding one request dict at a time — no
    materialized request list, so a 1M-request soak holds O(1) trace
    memory (the ROADMAP #18 down-payment; ``synthetic_trace`` below is the
    list-materializing wrapper every existing caller keeps using, and
    ``run_router_trace`` accepts the raw generator, submitting each
    request only when the clock reaches its arrival).

    Arrival-rate modulation (ISSUE 12 — the autoscaling workload shapes;
    both default OFF, and OFF is draw-for-draw identical to the historic
    trace for any seed):

    * ``diurnal`` in [0, 1): the instantaneous arrival rate is scaled by
      ``1 + diurnal * sin(2*pi*t / diurnal_period_blocks)`` — a smooth
      day/night load curve on the virtual clock (peak early in each
      period, trough in the second half). The mean stays
      ``mean_interarrival_blocks``-ish; the POINT is that a fixed fleet
      provisioned for the peak idles through the trough.
    * ``burst_every`` > 0: during the first quarter of every
      ``burst_every``-block window, arrivals come ``burst_mult``x faster —
      the square-wave flash-crowd shape that exercises scale-up patience
      and cooldown (a one-block spike must not spawn a replica; a
      sustained burst must).
    """
    import math
    if not 0.0 <= diurnal < 1.0:
        raise ValueError(f"diurnal must be in [0, 1), got {diurnal}")
    if diurnal_period_blocks < 1:
        raise ValueError(f"diurnal_period_blocks must be >= 1, got "
                         f"{diurnal_period_blocks}")
    if burst_every < 0:
        raise ValueError(f"burst_every must be >= 0, got {burst_every}")
    if burst_mult <= 0:
        raise ValueError(f"burst_mult must be > 0, got {burst_mult}")
    if long_prompt_frac < 0 or long_prompt_frac > 1:
        raise ValueError(f"long_prompt_frac must be in [0, 1], got {long_prompt_frac}")
    if long_prompt_frac > 0 and long_prompt_len < 1:
        raise ValueError("long_prompt_frac > 0 needs long_prompt_len >= 1")
    if tenants < 0:
        raise ValueError(f"tenants must be >= 0, got {tenants}")
    if tenant_skew < 0:
        raise ValueError(f"tenant_skew must be >= 0, got {tenant_skew}")
    if adapters < 0:
        raise ValueError(f"adapters must be >= 0, got {adapters}")
    if adapter_skew < 0:
        raise ValueError(f"adapter_skew must be >= 0, got {adapter_skew}")
    if not 0.0 <= grammar_frac <= 1.0:
        raise ValueError(f"grammar_frac must be in [0, 1], got {grammar_frac}")
    if grammar_frac > 0 and not grammars:
        raise ValueError("grammar_frac > 0 needs grammars=(names...)")
    if prefix_families < 1:
        raise ValueError(f"prefix_families must be >= 1, got {prefix_families}")
    long_every = round(1 / long_prompt_frac) if long_prompt_frac > 0 else 0
    rs = np.random.RandomState(seed)
    prefixes = [rs.randint(1, vocab_size,
                           (shared_prefix_len,)).astype(np.int32)
                for _ in range(prefix_families)]
    tenant_p = None
    if tenants:
        w = 1.0 / np.arange(1, tenants + 1, dtype=np.float64) ** tenant_skew
        tenant_p = w / w.sum()
    # structured-decoding labels ride their OWN stream (like adapters):
    # adding grammar labels never shifts the tenant/adapter/arrival draws,
    # and grammar_frac=0 is draw-for-draw identical to the historic trace
    grammar_rs = np.random.RandomState(seed + 0x67)
    grammar_count = 0
    adapter_p = None
    adapter_rs = np.random.RandomState(seed + 0x5A)   # independent stream
    if adapters:
        wa = 1.0 / np.arange(1, adapters + 1,
                             dtype=np.float64) ** adapter_skew
        adapter_p = wa / wa.sum()
    t = 0.0
    for i in range(num_requests):
        # instantaneous rate modulation (both factors 1.0 when off — the
        # exponential draw then consumes the identical scale, keeping the
        # stream draw-for-draw equal to the historic trace)
        rate = 1.0
        if diurnal > 0:
            rate *= max(1.0 + diurnal * math.sin(
                2.0 * math.pi * t / diurnal_period_blocks), 0.05)
        if burst_every and int(t) % burst_every < max(1, burst_every // 4):
            rate *= burst_mult
        t += rs.exponential(mean_interarrival_blocks / rate)
        s = int(prompt_lens[i % len(prompt_lens)])
        if long_every and i % long_every == long_every - 1:
            s = int(long_prompt_len)
        tail = rs.randint(1, vocab_size, (s,)).astype(np.int32)
        if tenant_p is not None:
            trace_tenant = f"t{int(rs.choice(tenants, p=tenant_p))}"
        prefix = prefixes[(i // 4) % prefix_families]
        item = {
            "prompt": np.concatenate([prefix, tail]) if shared_prefix_len else tail,
            "max_new_tokens": max_new_tokens,
            "eos_token_id": eos_token_id,
            "arrival_block": int(t),
            # per-request SLO budgets (None = none): the overload bench
            # attaches these to measure deadline-miss rate and goodput
            "ttft_deadline_ms": ttft_deadline_ms,
            "deadline_ms": deadline_ms,
        }
        if tenant_p is not None:
            item["tenant"] = trace_tenant
        if adapter_p is not None:
            item["adapter"] = \
                f"a{int(adapter_rs.choice(adapters, p=adapter_p))}"
        if grammar_frac > 0 and grammar_rs.random_sample() < grammar_frac:
            # cycle the grammar names over the CONSTRAINED subsequence so
            # every grammar sees traffic at any frac (pool churn included)
            item["grammar"] = grammars[grammar_count % len(grammars)]
            grammar_count += 1
        yield item


def synthetic_trace(num_requests: int, vocab_size: int,
                    **kw) -> List[dict]:
    """Deterministic synthetic arrival trace (virtual time in blocks):
    exponential inter-arrivals, prompt lengths cycled through
    ``prompt_lens`` — the multi-tenant workload shape the serving bench and
    the ``runner.py serve`` entrypoint replay. This is the materializing
    wrapper over :func:`synthetic_trace_stream` (same knobs, same draws —
    see there for the streamed form and the ``diurnal``/``burst_every``
    arrival-rate modulation). ``shared_prefix_len > 0``
    prepends a common random prefix of that many tokens to every prompt
    (the system-prompt / few-shot-header workload shape the paged engine's
    prefix cache exists for; prompt_lens then size the per-request tail);
    ``prefix_families > 1`` rotates through that many DISTINCT prefixes in
    runs of four consecutive requests (A A A A B B B B A ...) — the
    working-set-larger-than-the-pool workload the host tier exists for:
    the idle family's prefix goes cold, spills, and must restore (or
    re-prefill) when its run comes around again.

    ``long_prompt_frac > 0`` makes the prompt-length distribution heavy-
    tailed: every ``round(1/frac)``-th request (never the first, so decode
    traffic is already live when the first long prompt arrives) carries a
    ``long_prompt_len``-token prompt instead — the prefill/decode
    interference workload ``prefill_chunk_tokens`` exists for.

    ``tenants > 0`` labels each request with a tenant drawn from a
    Zipf-skewed distribution over ``t0..t<tenants-1>`` (P(rank k) ∝
    1/(k+1)^tenant_skew — t0 is the heavy hitter; skew 0 is uniform): the
    multi-tenant burst workload the Router's weighted fair queueing and
    tenant-aware shedding exist for. ``run_trace``/``run_router_trace``
    then report the per-tenant latency/goodput surface.

    ``adapters > 0`` labels each request with an adapter name drawn from
    its own Zipf distribution over ``a0..a<adapters-1>`` (independent
    stream — adding adapter labels never shifts the tenant draws): the
    every-user-their-own-fine-tune workload of the multi-LoRA pool. Low
    ``adapter_skew`` spreads traffic across adapters (pool churn when the
    pool holds fewer), high skew concentrates it (a0 stays hot). The
    caller must ``register_adapter`` every name the trace uses."""
    return list(synthetic_trace_stream(num_requests, vocab_size, **kw))


def per_tenant_report(completions: List[Completion],
                      tok_ts: Dict[int, np.ndarray], wall_s: float,
                      rejected_tenants: Sequence[str] = ()) -> Dict[str, dict]:
    """Per-tenant latency/goodput table (shared by :func:`run_trace` and the
    Router's report): delivery-gap ITL percentiles, TTFT, goodput (tokens of
    in-deadline streams only), and the shed/expiry counts — the isolation
    surface the fairness bench asserts on (one tenant's burst must not move
    another tenant's p99)."""
    rej = list(rejected_tenants)
    tenants = sorted({c.tenant for c in completions} | set(rej))
    out: Dict[str, dict] = {}
    for t in tenants:
        comps = [c for c in completions if c.tenant == t]
        gaps: List[float] = []
        for c in comps:
            ts = tok_ts.get(c.request_id, np.zeros((0,)))
            g = np.diff(ts) * 1e3 if ts.size > 1 else np.zeros((0,))
            gaps.extend(g[g > 0.0].tolist())
        ontime = sum(len(c.tokens) for c in comps
                     if not (c.deadline_missed or c.expired or c.cancelled))
        out[t] = {
            "requests": len(comps),
            # structured share per tenant (zero on free-form-only tenants)
            "constrained_requests": sum(1 for c in comps
                                        if c.grammar is not None),
            "generated_tokens": int(sum(len(c.tokens) for c in comps)),
            "itl_p50_ms": round(float(np.percentile(gaps, 50)), 3)
            if gaps else None,
            "itl_p99_ms": round(float(np.percentile(gaps, 99)), 3)
            if gaps else None,
            "ttft_blocks_mean": round(float(np.mean(
                [c.ttft_blocks for c in comps])), 2) if comps else None,
            "ttft_blocks_p99": int(np.percentile(
                [c.ttft_blocks for c in comps], 99)) if comps else None,
            "goodput_tokens_per_sec": (round(ontime / wall_s, 1)
                                       if wall_s > 0 else None),
            "rejected": rej.count(t),
            "expired": sum(1 for c in comps if c.expired),
            "deadline_missed": sum(1 for c in comps if c.deadline_missed),
        }
    return out


def interblock_gap_report(tracer: "Tracer", lanes: List[Any]) -> dict:
    """Summarise the dispatch-side pipeline health across one or more
    engine lanes (ROADMAP #22). Two distinct idle surfaces come out of the
    same dispatch/fetch spans:

    - ``interblock_gap_ms_*``: fetch(t) end -> dispatch(t+1) start — time
      the DEVICE sat idle while the host ran the scheduling pass. This is
      the number the async loop drives to ~0 (dispatch t+1 precedes
      fetch t, so the gap is 0 by construction).
    - ``fetch_blocked_ms_*``: the fetch span itself — time the HOST sat
      blocked waiting on the device. Sync pays scheduling + fetch serially;
      async pays only the residue of whatever device work the overlapped
      scheduling pass didn't cover.

    Returns ``{}`` when no paired spans exist (untraced engines, sim-only
    runs with < 2 decode blocks).
    """
    gaps: List[float] = []
    blocked: List[float] = []
    for lane in lanes:
        g, b = interblock_gaps(tracer, lane)
        gaps.extend(g)
        blocked.extend(b)
    if not gaps and not blocked:
        return {}
    out: dict = {}
    if gaps:
        out.update({
            "interblock_gap_ms_p50": round(float(np.percentile(gaps, 50)), 3),
            "interblock_gap_ms_p99": round(float(np.percentile(gaps, 99)), 3),
            "interblock_gap_ms_mean": round(float(np.mean(gaps)), 3),
        })
    if blocked:
        out.update({
            "fetch_blocked_ms_p50": round(float(np.percentile(blocked, 50)), 3),
            "fetch_blocked_ms_mean": round(float(np.mean(blocked)), 3),
        })
    return out


def run_trace(engine: ServeEngine, trace: List[dict],
              max_blocks: Optional[int] = None,
              snapshot_path: Optional[str] = None) -> dict:
    """Submit a synthetic trace and drive the engine to completion; returns
    the serving report (throughput, latency-in-blocks percentiles, wall
    TTFT/inter-token-latency surface, host-op accounting, and — when the
    trace carries deadlines or the engine bounds its queue — the overload
    surface: rejected/expired counts, deadline-miss rate, goodput) used by
    ``runner.py serve`` and the bench.

    The wall latency surface (inter-token delivery gaps, per-request max
    stall) is computed from the TRACER's per-request token events — the
    same single source of truth the Perfetto export and
    :meth:`ServeEngine.request_timeline` read — so this entrypoint turns
    tracing on when the engine was built without it. Callers measuring the
    untraced fast path (the tracing-overhead bench) drive ``engine.run()``
    directly.

    STREAMING MODE (``ServeEngine(keep_completions=False)``): the trace
    may be a raw generator — requests submit only when the virtual clock
    reaches their arrival, completions fold into counters and the engine's
    log-bucket latency histograms as they finish, and the report is built
    entirely from those aggregates (percentiles are histogram upper
    edges; no per-request lists, no tracer requirement) — the memory-
    bounded path million-request soaks run (ROADMAP #18)."""
    if not getattr(engine, "keep_completions", True):
        return _run_trace_streaming(engine, trace, max_blocks=max_blocks,
                                    snapshot_path=snapshot_path)
    if not isinstance(trace, (list, tuple)):
        # single-engine runs materialize a streamed trace (the streamed
        # submit-at-arrival path lives in run_router_trace)
        trace = list(trace)
    if not engine.tracer.enabled:
        engine.tracer.enabled = True
    tenant_of: Dict[int, str] = {}
    for item in trace:
        out = engine.submit(item["prompt"], item["max_new_tokens"],
                            eos_token_id=item.get("eos_token_id"),
                            arrival_block=item.get("arrival_block", 0),
                            ttft_deadline_ms=item.get("ttft_deadline_ms"),
                            deadline_ms=item.get("deadline_ms"),
                            tenant=item.get("tenant", "default"),
                            adapter=item.get("adapter"),
                            grammar=item.get("grammar"))
        rid = out.request_id if isinstance(out, Rejected) else out
        tenant_of[rid] = item.get("tenant", "default")
    t0 = time.perf_counter()
    completions = engine.run(max_blocks=max_blocks,
                             snapshot_path=snapshot_path)
    # conversation tier (--park-idle-blocks): the drain above leaves
    # auto-parked conversations durable but incomplete (parked streams
    # never block drain). Resume each — the finite trace's stand-in for
    # the user's return — and drain again until the trace is fully
    # served. "park_deferred" is a retry-later verdict (the next drain
    # frees the slot/pool it was waiting on); any other Rejected is
    # terminal and already accounted in engine.rejected.
    if getattr(engine, "park_idle_blocks", 0):
        dead = set()
        while True:
            pending = [r for r in engine.parked_ids() if r not in dead]
            if not pending:
                break
            resumed = 0
            for rid in pending:
                out = engine.submit(resume=rid)
                if isinstance(out, Rejected):
                    if out.reason != "park_deferred":
                        dead.add(rid)
                else:
                    resumed += 1
            if not resumed and not engine.step_block():
                break  # nothing resumable and the clock is drained
            # run() returns the engine's CUMULATIVE finish-order list, so
            # re-binding (not +=) keeps each request counted once
            completions = engine.run(max_blocks=max_blocks,
                                     snapshot_path=snapshot_path)
    wall_s = time.perf_counter() - t0
    total_tokens = int(sum(len(c.tokens) for c in completions))
    decode_blocks = max(engine.stats["decode_blocks"], 1)
    # wall-clock latency surface: per-request TTFT (virtual blocks — wall
    # arrivals would be backend-racy) and inter-token gaps from the
    # tracer's per-token delivery stamps. A fused block DELIVERS its K
    # tokens in one fetch (identical stamps), so the user-experienced
    # inter-token latency is the gap between successive deliveries —
    # intra-delivery zero gaps are excluded. A long-prompt one-shot insert
    # shows up as ONE huge delivery gap on every concurrently-decoding
    # request; chunked prefill bounds it, which is what pulls itl_p99 back
    # toward the no-insert per-block baseline.
    tok_ts = {
        rid: np.asarray([ev["ts"] for ev in evs if ev["name"] == "tok"],
                        np.float64)
        for rid, evs in engine.tracer.by_request().items()}
    per_request = []
    gaps_ms: List[float] = []
    for c in completions:
        ts = tok_ts.get(c.request_id, np.zeros((0,)))
        g = np.diff(ts) * 1e3 if ts.size > 1 else np.zeros((0,))
        g = g[g > 0.0]
        gaps_ms.extend(g.tolist())
        per_request.append({
            "request_id": c.request_id,
            "prompt_len": c.prompt_len,
            "generated": int(len(c.tokens)),
            "ttft_blocks": c.ttft_blocks,
            "max_itl_gap_ms": round(float(g.max()), 2) if g.size else 0.0,
        })
    report = {
        "requests_completed": len(completions),
        "total_generated_tokens": total_tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_sec": round(total_tokens / wall_s, 1) if wall_s > 0 else None,
        "blocks": engine.stats["blocks"],
        "decode_blocks": engine.stats["decode_blocks"],
        "block_steps": engine.block_steps,
        "fused": engine.fused,
        "inserts": engine.stats["inserts"],
        "inserted_requests": engine.stats["inserted_requests"],
        "program_calls": engine.stats["program_calls"],
        "host_fetches": engine.stats["host_fetches"],
        # the dispatch contract the fused path exists for: decode-side host
        # ops (program call + fetch) per K-token block of the whole pool;
        # 2.0 with fused=True, 2*K with fused=False (inserts accounted
        # separately above)
        "host_ops_per_block": round(
            (engine.stats["program_calls"] + engine.stats["host_fetches"])
            / decode_blocks, 2),
        # pipeline surface: device idle between blocks (the async loop's
        # target metric) and host time blocked in fetches — see
        # interblock_gap_report for the span pairing
        "async_loop": engine.async_loop,
        **interblock_gap_report(engine.tracer, [engine.lane]),
        "queue_blocks_mean": round(float(np.mean(
            [c.queue_blocks for c in completions])), 2) if completions else None,
        "decode_blocks_mean": round(float(np.mean(
            [c.decode_blocks for c in completions])), 2) if completions else None,
        # chunked-prefill surface (zeros when prefill_chunk_tokens == 0)
        "prefill_chunk_tokens": engine.prefill_chunk_tokens,
        "chunk_program_calls": engine.stats["chunk_program_calls"],
        "prefill_chunk_tokens_done": engine.stats["prefill_chunk_tokens_done"],
        "prefill_aborts": engine.stats["prefill_aborts"],
        # latency surface
        "ttft_blocks_mean": round(float(np.mean(
            [c.ttft_blocks for c in completions])), 2) if completions else None,
        "ttft_blocks_max": int(max(c.ttft_blocks for c in completions))
        if completions else None,
        "itl_p50_ms": round(float(np.percentile(gaps_ms, 50)), 3)
        if gaps_ms else None,
        "itl_p99_ms": round(float(np.percentile(gaps_ms, 99)), 3)
        if gaps_ms else None,
        "max_itl_gap_ms": round(float(np.max(gaps_ms)), 2)
        if gaps_ms else None,
        "per_request": per_request,
    }
    # overload / robustness surface: rejected-by-shedding, expired-by-
    # deadline, miss rate over ALL submissions (shed counts as a miss — a
    # rejected client got nothing, exactly like a blown deadline, just
    # cheaply and immediately), and GOODPUT: only tokens of requests that
    # completed within their deadlines count
    submitted = len(trace)
    rejected = len(engine.rejected)
    expired = sum(1 for c in completions if c.expired)
    missed = sum(1 for c in completions if c.deadline_missed)
    has_deadlines = any(item.get("deadline_ms") or item.get("ttft_deadline_ms")
                        for item in trace)
    ontime_tokens = sum(
        len(c.tokens) for c in completions
        if not (c.deadline_missed or c.expired or c.cancelled))
    report.update({
        "rejected": rejected,
        "expired": expired,
        "shed_evictions": engine.stats["shed_evictions"],
        "max_queue": engine.max_queue,
        "shed_policy": engine.shed_policy,
        "deadline_miss_rate": (round((rejected + missed) / submitted, 4)
                               if has_deadlines and submitted else None),
        "goodput_tokens_per_sec": (round(ontime_tokens / wall_s, 1)
                                   if wall_s > 0 else None),
        "dispatch_retries": engine.stats["dispatch_retries"],
        "corrupt_page_replays": engine.stats["corrupt_page_replays"],
        "restored_requests": engine.stats["restored_requests"],
        # tracing surface: how much of the timeline survives in the ring
        # buffer (dropped > 0 means the export window is partial)
        "trace_events": len(engine.tracer.events()),
        "trace_events_dropped": engine.tracer.dropped,
    })
    if engine.park_store is not None:
        # conversation-tier surface: parked_remaining > 0 means the trace
        # ended with conversations still durable on disk (their bytes are
        # the tier's footprint — device and host hold ZERO for them)
        report.update({
            "park_idle_blocks": engine.park_idle_blocks,
            "parked": engine.stats["parked"],
            "resumed": engine.stats["resumed"],
            "park_replays": engine.stats["park_replays"],
            "park_rejects": engine.stats["park_rejects"],
            "parked_remaining": len(engine.parked_ids()),
            "parked_bytes": int(sum(
                engine.park_store.parked_bytes(r)
                for r in engine.park_store.list_parked())),
        })
    # per-tenant isolation surface (present whenever the trace labels
    # tenants): the aggregate numbers above hide exactly the thing a quota
    # system exists to protect — whose p99 a burst moved
    if any(t != "default" for t in tenant_of.values()):
        report["per_tenant"] = per_tenant_report(
            completions, tok_ts, wall_s,
            [tenant_of.get(r.request_id, "default")
             for r in engine.rejected])
    if getattr(engine, "grammar", False):
        # structured-decoding surface (ISSUE 13): the constrained share of
        # the trace and its latency split vs the free-form tenants riding
        # the same pool — the "masking must not stall the pool" evidence —
        # plus the pool's load/evict/repair cycle and finish reasons
        gpool = engine.session.grammars

        def _split(pred):
            comps = [c for c in completions if pred(c)]
            gaps: List[float] = []
            for c in comps:
                ts = tok_ts.get(c.request_id, np.zeros((0,)))
                gg = np.diff(ts) * 1e3 if ts.size > 1 else np.zeros((0,))
                gaps.extend(gg[gg > 0.0].tolist())
            return {
                "requests": len(comps),
                "itl_p50_ms": round(float(np.percentile(gaps, 50)), 3)
                if gaps else None,
                "itl_p99_ms": round(float(np.percentile(gaps, 99)), 3)
                if gaps else None,
                "ttft_blocks_mean": round(float(np.mean(
                    [c.ttft_blocks for c in comps])), 2) if comps else None,
            }

        constrained = [c for c in completions if c.grammar is not None]
        report["structured"] = {
            "constrained_requests": len(constrained),
            "constrained_share": (round(len(constrained) / len(completions),
                                        3) if completions else None),
            "constrained": _split(lambda c: c.grammar is not None),
            "freeform": _split(lambda c: c.grammar is None),
            "finish_reasons": {
                r: sum(1 for c in completions if c.finish_reason == r)
                for r in sorted({c.finish_reason for c in completions})},
            "grammar_slots": gpool.n_slots,
            "grammars_resident": sorted(gpool.resident),
            "grammar_loads": gpool.stats["loads"],
            "grammar_evictions": gpool.stats["evictions"],
            "grammar_hits": gpool.stats["hits"],
            "grammar_repairs": gpool.stats["repairs"],
            "grammar_rejects": engine.stats["grammar_rejects"],
            "grammar_load_retries": engine.stats["grammar_load_retries"],
            "grammar_bytes_per_slot": gpool.grammar_bytes(),
            "grammar_compile_ms": {
                n: gpool.compile_ms_of(n) for n in sorted(gpool._registry)},
        }
    if getattr(engine, "lora", False):
        # multi-LoRA surface: pool residency + the load/evict/repair cycle
        # — the "one compiled program, any adapter mix" evidence
        pool = engine.session.adapters
        report.update({
            "multilora": True,
            "adapter_slots": pool.n_slots,
            "adapters_resident": sorted(pool.resident),
            "adapter_loads": pool.stats["loads"],
            "adapter_evictions": pool.stats["evictions"],
            "adapter_hits": pool.stats["hits"],
            "adapter_repairs": pool.stats["repairs"],
            "adapter_load_failures": pool.stats["load_failures"],
            "adapter_rejects": engine.stats["adapter_rejects"],
            "adapter_load_retries": engine.stats["adapter_load_retries"],
            "adapter_bytes_per_slot": pool.adapter_bytes(),
        })
    if engine._injector is not None:
        report["fault_stats"] = dict(engine._injector.stats)
    pkv = getattr(engine.session, "paged", None)
    if pkv is not None:
        kv = engine.lm.kv_cache_bytes()
        report.update({
            "paged": True,
            "page_size": pkv.page_size,
            "page_pool_pages": pkv.num_pages,
            # what the pool bytes below were measured under
            "page_dtype": engine._page_dtype(),
            "prefix_queries": pkv.stats["prefix_queries"],
            "prefix_hits": pkv.stats["prefix_hits"],
            "prefix_hit_tokens": pkv.stats["prefix_hit_tokens"],
            "pages_in_use_peak": pkv.stats["pages_in_use_peak"],
            "evicted_pages": pkv.stats["evicted_pages"],
            "deferred_admissions": engine.stats["deferred_admissions"],
            "kv_hbm_bytes": kv["kv_bytes"],
            "kv_hbm_bytes_global": kv["kv_bytes_global"],
            "kv_slab_hbm_bytes": kv["kv_slab_bytes"],
            "kv_hbm_vs_slab": round(kv["kv_bytes"] / kv["kv_slab_bytes"], 3),
        })
        from neuronx_distributed_tpu.inference.partition import (
            sharded_fraction, tp_degree,
        )
        report.update({
            # TP-sharded serving surface: per-chip vs global KV bytes is
            # the capacity-multiplication evidence (ISSUE 16)
            "tp_degree": tp_degree(),
            "kv_sharded_fraction": round(
                sharded_fraction(engine.session.cache), 3),
        })
        if pkv.tier is not None:
            # host-tier surface: the spill/restore/repair cycle plus what
            # is resident right now — the "pool pressure became latency,
            # not sheds" evidence
            report.update({
                "host_tier_pages": pkv.tier.max_pages,
                "tier_pages_resident": pkv.tier_pages(),
                "tier_bytes_resident": pkv.tier_bytes(),
                "tier_spilled_pages": pkv.stats["tier_spilled_pages"],
                "tier_restored_pages": pkv.stats["tier_restored_pages"],
                "tier_hits": pkv.stats["tier_hits"],
                "tier_restore_failures": pkv.stats["tier_restore_failures"],
                "tier_repaired_pages": pkv.stats["tier_repaired_pages"],
                "tier_restore_ms_p99": (
                    round(float(np.percentile(pkv._restore_ms, 99)), 3)
                    if pkv._restore_ms else None),
            })
    return report


def _submit_item(submit, item) -> None:
    """Submit one synthetic-trace dict through ``submit`` (the engine's or
    the router's) — the one place the trace-item schema is interpreted."""
    submit(item["prompt"], item["max_new_tokens"],
           eos_token_id=item.get("eos_token_id"),
           arrival_block=item.get("arrival_block", 0),
           ttft_deadline_ms=item.get("ttft_deadline_ms"),
           deadline_ms=item.get("deadline_ms"),
           tenant=item.get("tenant", "default"),
           adapter=item.get("adapter"),
           grammar=item.get("grammar"))


def _run_trace_streaming(engine: ServeEngine, trace,
                         max_blocks: Optional[int] = None,
                         snapshot_path: Optional[str] = None) -> dict:
    """Memory-bounded run_trace (``keep_completions=False``): submit at
    arrival off a raw iterator, report entirely from the stats counters
    and log-bucket histograms — O(in-flight) host memory regardless of
    trace length, zero tracer requirement (ROADMAP #18)."""
    if snapshot_path is not None:
        raise ValueError("streaming runs do not snapshot (keep_completions"
                         "=False drops the per-request record the snapshot"
                         " would serialize)")
    it = iter(trace)
    nxt = next(it, None)
    submitted = 0
    has_deadlines = False
    t0 = time.perf_counter()
    n = 0
    while True:
        while (nxt is not None
               and int(nxt.get("arrival_block", 0)) <= engine.blocks):
            _submit_item(engine.submit, nxt)
            submitted += 1
            has_deadlines = has_deadlines or bool(
                nxt.get("deadline_ms") or nxt.get("ttft_deadline_ms"))
            nxt = next(it, None)
        more = engine.step_block()
        n += 1
        if max_blocks is not None and n >= max_blocks:
            break
        if not more and nxt is None:
            break
    engine._sync_compile_metrics()
    wall_s = time.perf_counter() - t0
    st = engine.stats
    completed = int(st["completed"])
    total_tokens = int(st["generated_tokens"])
    decode_blocks = max(int(st["decode_blocks"]), 1)
    itl = engine._m_itl
    rejected = int(st["rejected"])
    missed = int(st["deadline_misses"])
    return {
        "streaming": True,
        "percentile_basis": "log-bucket histogram upper edges",
        "requests_submitted": submitted,
        "requests_completed": completed,
        "total_generated_tokens": total_tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_sec": (round(total_tokens / wall_s, 1)
                           if wall_s > 0 else None),
        "goodput_tokens_per_sec": (
            round(int(st["ontime_tokens"]) / wall_s, 1)
            if wall_s > 0 else None),
        "sched_overhead_us_per_request": (
            round(wall_s * 1e6 / completed, 2) if completed else None),
        "blocks": int(st["blocks"]),
        "decode_blocks": int(st["decode_blocks"]),
        "block_steps": engine.block_steps,
        "fused": engine.fused,
        "inserts": int(st["inserts"]),
        "inserted_requests": int(st["inserted_requests"]),
        "host_ops_per_block": round(
            (int(st["program_calls"]) + int(st["host_fetches"]))
            / decode_blocks, 2),
        "queue_blocks_mean": (round(int(st["queue_blocks_sum"])
                                    / completed, 2) if completed else None),
        "ttft_blocks_mean": (round(int(st["ttft_blocks_sum"])
                                   / completed, 2) if completed else None),
        "itl_p50_ms": (round(itl.percentile(50), 3)
                       if itl.count else None),
        "itl_p99_ms": (round(itl.percentile(99), 3)
                       if itl.count else None),
        "rejected": rejected,
        "expired": int(st["expired"]),
        "shed_evictions": int(st["shed_evictions"]),
        "deadline_miss_rate": (
            round((rejected + missed) / submitted, 4)
            if has_deadlines and submitted else None),
        "deferred_admissions": int(st["deferred_admissions"]),
        "dispatch_retries": int(st["dispatch_retries"]),
    }


def run_router_trace(router: Router, trace,
                     max_blocks: Optional[int] = None) -> dict:
    """Submit a synthetic trace to the Router and drive the fleet to
    completion; returns the serving report in ``run_trace``'s shape plus
    the router surface (per-replica states, placements, failovers, drains)
    and the per-tenant isolation table. Turns tracing on (the wall
    ITL surface reads the shared tracer's token events) exactly like
    ``run_trace``.

    ``trace`` is a list (submitted up-front, the historic shape) or ANY
    iterator — e.g. the raw :func:`synthetic_trace_stream` generator: the
    streamed form pulls one item at a time and submits it only once the
    shared clock reaches its arrival block, so the request list is never
    materialized (ROADMAP #18 down-payment) and the run keeps the clock
    alive through arrival gaps — the idle valleys autoscaling scales down
    into. Token streams are identical either way (the per-request rng
    contract); WFQ tags and wall accounting differ slightly in basis
    (streamed submission happens inside the timed loop).

    STREAMING REPORT (``Router(keep_completions=False)``): tracing is NOT
    force-enabled, no per-request lists are materialized anywhere — the
    report reads the harvest aggregates and the per-replica log-bucket
    latency histograms merged explicitly (percentiles are bucket upper
    edges). The memory-bounded mode the 1M-request soak runs
    (``scripts/soak.py`` — ROADMAP #18)."""
    streaming = not getattr(router, "keep_completions", True)
    if not streaming and not router.tracer.enabled:
        router.tracer.enabled = True
    # O(1)-per-request bookkeeping (tenant label + deadline flag) — the
    # report's denominator; deliberately NOT the items themselves
    meta: List[Tuple[str, bool]] = []
    counts = {"submitted": 0, "deadlines": False}

    def _submit(item):
        router.submit(item["prompt"], item["max_new_tokens"],
                      eos_token_id=item.get("eos_token_id"),
                      arrival_block=item.get("arrival_block", 0),
                      ttft_deadline_ms=item.get("ttft_deadline_ms"),
                      deadline_ms=item.get("deadline_ms"),
                      tenant=item.get("tenant", "default"),
                      adapter=item.get("adapter"),
                      grammar=item.get("grammar"))
        counts["submitted"] += 1
        counts["deadlines"] = counts["deadlines"] or bool(
            item.get("deadline_ms") or item.get("ttft_deadline_ms"))
        if not streaming:
            meta.append((item.get("tenant", "default"),
                         bool(item.get("deadline_ms")
                              or item.get("ttft_deadline_ms"))))

    if isinstance(trace, (list, tuple)):
        for item in trace:
            _submit(item)
        t0 = time.perf_counter()
        completions = router.run(max_blocks=max_blocks)
        wall_s = time.perf_counter() - t0
    else:
        it = iter(trace)
        nxt = next(it, None)
        t0 = time.perf_counter()
        n = 0
        while True:
            while (nxt is not None
                   and int(nxt.get("arrival_block", 0)) <= router.blocks):
                _submit(nxt)
                nxt = next(it, None)
            more = router.step_block()
            n += 1
            if max_blocks is not None and n >= max_blocks:
                break
            if not more and nxt is None:
                break
        completions = router.completed
        wall_s = time.perf_counter() - t0
    if streaming:
        return _streaming_router_report(router, wall_s,
                                        counts["submitted"],
                                        counts["deadlines"])
    total_tokens = int(sum(len(c.tokens) for c in completions))
    tok_ts = {
        rid: np.asarray([ev["ts"] for ev in evs if ev["name"] == "tok"],
                        np.float64)
        for rid, evs in router.tracer.by_request().items()}
    gaps_ms: List[float] = []
    for c in completions:
        ts = tok_ts.get(c.request_id, np.zeros((0,)))
        g = np.diff(ts) * 1e3 if ts.size > 1 else np.zeros((0,))
        gaps_ms.extend(g[g > 0.0].tolist())
    submitted = len(meta)
    rejected = len(router.rejected)
    expired = sum(1 for c in completions if c.expired)
    missed = sum(1 for c in completions if c.deadline_missed)
    has_deadlines = any(flag for _t, flag in meta)
    ontime_tokens = sum(
        len(c.tokens) for c in completions
        if not (c.deadline_missed or c.expired or c.cancelled))
    report = {
        "replicas": len(router.engines),
        "placement": router.placement,
        "requests_completed": len(completions),
        "total_generated_tokens": total_tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_sec": (round(total_tokens / wall_s, 1)
                           if wall_s > 0 else None),
        "goodput_tokens_per_sec": (round(ontime_tokens / wall_s, 1)
                                   if wall_s > 0 else None),
        "blocks": router.blocks,
        "rejected": rejected,
        "expired": expired,
        "deadline_miss_rate": (round((rejected + missed) / submitted, 4)
                               if has_deadlines and submitted else None),
        "itl_p50_ms": round(float(np.percentile(gaps_ms, 50)), 3)
        if gaps_ms else None,
        "itl_p99_ms": round(float(np.percentile(gaps_ms, 99)), 3)
        if gaps_ms else None,
        "ttft_blocks_mean": round(float(np.mean(
            [c.ttft_blocks for c in completions])), 2)
        if completions else None,
        # pipeline surface aggregated over every replica lane that ever
        # dispatched (parked replicas contribute no spans)
        "async_loop": any(getattr(e, "async_loop", False)
                          for e in router.engines if e is not None),
        **interblock_gap_report(
            router.tracer,
            [e.lane for e in router.engines if e is not None]),
        # provisioned capacity actually consumed (replica-blocks): the
        # denominator of the autoscale-vs-fixed goodput-per-capacity key
        "replica_blocks": router.stats["replica_blocks"],
        "placements": router.stats["placements"],
        "affinity_placements": router.stats["affinity_placements"],
        "requeues": router.stats["requeues"],
        "crashes": router.stats["crashes"],
        "failovers": router.stats["failovers"],
        "failed_over_requests": router.stats["failed_over_requests"],
        "drains": router.stats["drains"],
        "last_failover_ms": router.last_failover_ms,
        "last_drain_ms": router.last_drain_ms,
        "replica_states": router.replica_states(),
        "trace_events": len(router.tracer.events()),
        "trace_events_dropped": router.tracer.dropped,
    }
    tiered = [eng.session.paged for eng in router.engines
              if eng.paged and eng.session.paged is not None
              and eng.session.paged.tier is not None]
    if tiered:
        # fleet-aggregate host-tier surface (per-replica residency is in
        # replica_states): spills/restores/repairs summed across replicas
        report.update({
            "tier_pages_resident": sum(p.tier_pages() for p in tiered),
            "tier_spilled_pages": sum(
                p.stats["tier_spilled_pages"] for p in tiered),
            "tier_restored_pages": sum(
                p.stats["tier_restored_pages"] for p in tiered),
            "tier_restore_failures": sum(
                p.stats["tier_restore_failures"] for p in tiered),
            "tier_repaired_pages": sum(
                p.stats["tier_repaired_pages"] for p in tiered),
        })
    lora_engines = [eng for eng in router.engines
                    if getattr(eng, "lora", False)]
    if lora_engines:
        # fleet-aggregate multi-LoRA surface (per-replica residency is in
        # replica_states): loads/evictions/repairs summed across replicas
        report.update({
            "multilora": True,
            "adapter_loads": sum(
                eng.session.adapters.stats["loads"] for eng in lora_engines),
            "adapter_evictions": sum(
                eng.session.adapters.stats["evictions"]
                for eng in lora_engines),
            "adapter_repairs": sum(
                eng.session.adapters.stats["repairs"]
                for eng in lora_engines),
            "adapter_rejects": sum(
                int(eng.stats["adapter_rejects"]) for eng in lora_engines),
        })
    tenants = {t for t, _flag in meta}
    if tenants != {"default"}:
        report["per_tenant"] = per_tenant_report(
            completions, tok_ts, wall_s,
            [router._tenant_of.get(r.request_id, "default")
             for r in router.rejected])
    if router._injector is not None:
        report["fault_stats"] = dict(router._injector.stats)
    if router.autoscaler is not None:
        # elastic-fleet surface: the deterministic scale-event log plus
        # warm/cold spawn counts and scale-up time-to-ready blocks
        report["autoscale"] = router.autoscaler.report(router)
    return report


def _streaming_router_report(router: Router, wall_s: float,
                             submitted: int, has_deadlines: bool) -> dict:
    """Memory-bounded fleet report (``keep_completions=False``): built from
    the harvest aggregates and the per-replica latency histograms merged
    bucket-wise — no per-request lists, no tracer (ROADMAP #18)."""
    agg = router._agg
    completed = agg["completed"]
    total_tokens = agg["tokens"]
    itls = [eng._m_itl for eng in router.engines]
    ttfts = [eng._m_ttft for eng in router.engines]
    itl = itls[0].merged(*itls[1:]) if itls else None
    ttft = ttfts[0].merged(*ttfts[1:]) if ttfts else None
    rejected = int(router.stats["rejected"])
    report = {
        "streaming": True,
        "percentile_basis": "log-bucket histogram upper edges",
        "replicas": len(router.engines),
        "placement": router.placement,
        "requests_submitted": submitted,
        "requests_completed": completed,
        "total_generated_tokens": total_tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_sec": (round(total_tokens / wall_s, 1)
                           if wall_s > 0 else None),
        "goodput_tokens_per_sec": (
            round(agg["ontime_tokens"] / wall_s, 1) if wall_s > 0 else None),
        # the ROADMAP #18 deliverable: total host wall over completed
        # requests — with a sim lm there is no device time to hide behind,
        # so this IS the scheduler+bookkeeping cost per request
        "sched_overhead_us_per_request": (
            round(wall_s * 1e6 / completed, 2) if completed else None),
        "blocks": router.blocks,
        "rejected": rejected,
        "expired": agg["expired"],
        "cancelled": agg["cancelled"],
        "deadline_miss_rate": (
            round((rejected + agg["missed"]) / submitted, 4)
            if has_deadlines and submitted else None),
        "itl_p50_ms": (round(itl.percentile(50), 3)
                       if itl is not None and itl.count else None),
        "itl_p99_ms": (round(itl.percentile(99), 3)
                       if itl is not None and itl.count else None),
        "ttft_ms_p99": (round(ttft.percentile(99), 3)
                        if ttft is not None and ttft.count else None),
        "ttft_blocks_mean": (round(agg["ttft_blocks_sum"] / completed, 2)
                             if completed else None),
        "queue_blocks_mean": (round(agg["queue_blocks_sum"] / completed, 2)
                              if completed else None),
        "replica_blocks": router.stats["replica_blocks"],
        "placements": router.stats["placements"],
        "affinity_placements": router.stats["affinity_placements"],
        "requeues": router.stats["requeues"],
        "crashes": router.stats["crashes"],
        "failovers": router.stats["failovers"],
        "drains": router.stats["drains"],
        "replicas_active": len(router._live_replicas()),
    }
    if router.autoscaler is not None:
        report["autoscale"] = router.autoscaler.report(router)
    return report


def decode_clock_itl(router: DisaggRouter,
                     long_prompt_cutoff: Optional[int] = None) -> dict:
    """Decode-side latency surface on the per-worker clock: each stream's
    token i is stamped with its home decode worker's CUMULATIVE wall
    seconds through the block that delivered it (that worker's dispatches,
    fetches, and adoption writes only — not the co-scheduled prefill
    workers this single-threaded harness interleaves). Returns delivery-gap
    percentiles plus the long-prompt interference verdict:
    ``decode_stall_excess_ms`` — the worst gap a SHORT request saw beyond
    the run's median gap (``long_prompt_cutoff`` defaults to the longest
    prompt in the run, so "short" = everything shorter than the tail). On
    a fleet where prompts never touch decode workers this is ≈ 0 — the
    number chunked prefill could only bound, eliminated."""
    tok_blocks: Dict[int, List[int]] = {}
    for rid, evs in router.tracer.by_request().items():
        tok_blocks[rid] = [ev["block"] for ev in evs
                           if ev["name"] == "tok" and ev["block"] is not None]
    cum = {j: np.cumsum(np.asarray(w, np.float64))
           for j, w in enumerate(router._eng_block_wall)}
    gaps_ms: List[float] = []
    handoff_gaps_ms: List[float] = []
    short_max: List[float] = []
    all_max: List[float] = []
    plens = {c.request_id: c.prompt_len for c in router.completed}
    if long_prompt_cutoff is None:
        long_prompt_cutoff = max(plens.values(), default=0)
    for c in router.completed:
        j = router._decode_home.get(c.request_id)
        blocks = tok_blocks.get(c.request_id)
        if j is None or not blocks or cum[j].size == 0:
            continue
        ts = np.asarray([cum[j][min(b, cum[j].size - 1)] for b in blocks])
        g_all = np.diff(ts) * 1e3
        if g_all.size:
            # the token0→token1 gap is MIGRATION latency, not decode ITL:
            # token 0 lands early on the prefill side and the stream then
            # waits for adoption + a decode slot — that wait is reported
            # separately (and attributed to the 'migration' phase); the
            # steady-state decode surface starts at token 1
            handoff_gaps_ms.append(float(g_all[0]))
            g = g_all[1:]
        else:
            g = g_all
        g = g[g > 0.0]
        gaps_ms.extend(g.tolist())
        if g.size:
            all_max.append(float(g.max()))
            if c.prompt_len < long_prompt_cutoff:
                short_max.append(float(g.max()))
    p50 = round(float(np.percentile(gaps_ms, 50)), 3) if gaps_ms else None
    p99 = round(float(np.percentile(gaps_ms, 99)), 3) if gaps_ms else None
    if not short_max:
        short_max = all_max      # uniform-length trace: no tail to exclude
    excess = None
    if short_max and p50 is not None:
        excess = round(max(0.0, max(short_max) - p50), 3)
    return {
        "itl_p50_ms_decode_clock": p50,
        "itl_p99_ms_decode_clock": p99,
        "decode_stall_excess_ms": excess,
        "handoff_gap_ms_p99": (
            round(float(np.percentile(handoff_gaps_ms, 99)), 3)
            if handoff_gaps_ms else None),
    }


def run_disagg_trace(router: DisaggRouter, trace: List[dict],
                     max_blocks: Optional[int] = None) -> dict:
    """Drive a synthetic trace through the disaggregated fleet; returns
    ``run_router_trace``'s report plus the disaggregation surface: roles,
    the handoff lifecycle counters, and the decode-clock latency numbers
    (see :func:`decode_clock_itl` for the clock's basis — the in-process
    wall ``itl_*`` keys remain in the report for the caveat trail)."""
    report = run_router_trace(router, trace, max_blocks=max_blocks)
    long_lens = [len(item["prompt"]) for item in trace]
    cutoff = max(long_lens) if long_lens else None
    report.update({
        "disagg": True,
        "prefill_replicas": router.prefill_replicas,
        "decode_replicas": len(router.engines) - router.prefill_replicas,
        "handoffs_sent": router.stats["handoffs_sent"],
        "handoffs_adopted": router.stats["handoffs_adopted"],
        "handoffs_degraded": router.stats["handoffs_degraded"],
        "handoffs_deferred": router.stats["handoffs_deferred"],
        "handoff_pages": router.stats["handoff_pages"],
        "adopted_pages": sum(
            eng.session.paged.stats["adopted_pages"]
            for eng in router.engines if eng.session.paged is not None),
    })
    report.update(decode_clock_itl(router, long_prompt_cutoff=cutoff))
    return report
