"""Host-memory KV tier (ISSUE 8 tentpole gates).

The contract under test: pool exhaustion becomes a spill/restore cycle
instead of a shed/drop event, and NOTHING about it may move a token.

* EXACTNESS ORACLE — streams served through spill + restore (including
  restore-mid-chunked-prefill and snapshot/restore of a tiered engine) are
  bit-identical to an untiered engine with an effectively infinite pool,
  across fused/stepwise x greedy/sampled;
* DEGRADATION LADDER — a restore that fails (seeded ``tier`` fault seam) or
  whose host bytes are corrupted (caught by the per-page checksum)
  invalidates the subtree and re-prefills: a latency event, never a wrong
  token, and the same fault plan replayed twice makes identical decisions;
* INCLUSIVE-TIER REPAIR — a corrupted DEVICE page whose radix entry still
  holds a checksum-valid host copy is repaired in place (no replay, no
  subtree invalidation) — even while a live stream reads through it;
* NO LEAK — after chaos (pool storms + corruption + tier faults) the
  allocator AND the tier both drain to zero once the cache is dropped.

Tier-1 cost discipline: one module-scoped params set behind both lms
(block_steps=4, tiny 2-layer config — the sibling suites' shapes). The
tier is per-ENGINE (host-side only), so tiered and untiered runs share one
compiled lm.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    FaultPlan,
    Sampler,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.replay import run_trace, synthetic_trace
from neuronx_distributed_tpu.inference.paged_cache import (
    HostPageTier,
    TierCorruption,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
PAGE = 4
SMALL_POOL = 13     # 3 scratch + 10 allocatable: real pressure at tiny scale
TIER = 32


@pytest.fixture(scope="module")
def stack():
    """(big-pool paged lm — the 'infinite pool' untiered oracle — and a
    small-pool paged lm the tier tests pressure) over ONE weight set."""
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm_big = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                      max_batch=3, page_size=PAGE).compile()
    lm_small = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                        max_batch=3, page_size=PAGE,
                        page_pool_pages=SMALL_POOL).compile()
    return cfg, params, lm_big, lm_small


def _family(seed, n_tails, tail=8):
    """One shared-prefix family: n_tails prompts over a common 8-token
    prefix (2 full pages under the (plen-1)//page clamp)."""
    rs = np.random.RandomState(seed)
    prefix = rs.randint(1, 127, (8,)).astype(np.int32)
    return [np.concatenate([prefix,
                            rs.randint(1, 127, (tail,)).astype(np.int32)])
            for _ in range(n_tails)]


def _pressure_submits():
    """A-family request, a concurrent B-family burst big enough to spill
    A's prefix out of the small pool, then A again (restore on hit).
    Mixes greedy and sampled."""
    a = _family(1, 2)
    b = _family(2, 3)
    return ([dict(prompt=a[0], max_new_tokens=8)]
            + [dict(prompt=p, max_new_tokens=8, arrival_block=4,
                    sampler=(Sampler(temperature=1.1) if i == 1 else None))
               for i, p in enumerate(b)]
            + [dict(prompt=a[1], max_new_tokens=8, arrival_block=12,
                    sampler=Sampler(temperature=0.8))])


def _streams(engine):
    return {c.request_id: c.tokens.tolist() for c in engine.completed}


def _run(lm, submits, **kw):
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42), **kw)
    for s in submits:
        eng.submit(**s)
    eng.run(max_blocks=300)
    return eng


def _drain_all(pkv):
    if pkv.prefix is not None:
        pkv.prefix.drop_tiered()
        pkv.prefix.evict(10 ** 6)


# ------------------------------------------------------- exactness oracle

def test_tiered_streams_bit_identical_across_modes(stack):
    """THE acceptance gate: spill + restore happened (stats prove it) and
    every stream equals the infinite-pool untiered oracle, fused AND
    stepwise, greedy AND sampled."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    oracle = _streams(_run(lm_big, submits))
    for fused in (True, False):
        eng = _run(lm_small, submits, fused=fused, host_tier_pages=TIER)
        pkv = eng.session.paged
        assert pkv.stats["tier_spilled_pages"] > 0, fused
        assert pkv.stats["tier_restored_pages"] > 0, fused
        assert pkv.stats["tier_hits"] > 0, fused
        assert _streams(eng) == oracle, fused
        _drain_all(pkv)
        assert pkv.allocator.in_use() == 0 and pkv.tier_pages() == 0


def test_restore_mid_chunked_prefill_exact(stack):
    """A chunked admission whose shared prefix sits in the HOST tier:
    ``begin_chunked`` restores it (earlier ``start``), the remaining
    chunks prefill, and the stream is bit-identical to the oracle."""
    cfg, params, lm_big, lm_small = stack
    a = _family(5, 2, tail=8)
    long_tail = _family(5, 1, tail=8)[0]   # same prefix, fresh tail
    submits = [dict(prompt=a[0], max_new_tokens=6),
               dict(prompt=a[1], max_new_tokens=6, arrival_block=3),
               dict(prompt=long_tail, max_new_tokens=6, arrival_block=8,
                    sampler=Sampler(temperature=1.2))]
    oracle = _streams(_run(lm_big, submits, prefill_chunk_tokens=5))
    eng = ServeEngine(lm_small, block_steps=K, prefill_chunk_tokens=5,
                      rng=jax.random.key(42), host_tier_pages=TIER)
    for s in submits[:2]:
        eng.submit(**s)
    eng.run()
    pkv = eng.session.paged
    # push the whole cache (the shared prefix included) into the tier,
    # then admit the chunk-eligible request: begin_chunked must restore
    spilled = pkv.prefix.spill(10 ** 6)
    assert spilled > 0 and pkv.allocator.in_use() == 0
    eng.submit(**submits[2])
    eng.run()
    assert pkv.stats["tier_restored_pages"] > 0
    assert eng.stats["chunk_program_calls"] > 0
    assert _streams(eng) == oracle


def test_snapshot_of_tiered_engine_restores_bit_identical(stack):
    """Snapshot/restore PINS the tier policy: content is dropped (host
    buffers die with the process), the knob survives in the config, and
    the restored engine's replayed streams equal the oracle."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    oracle = _streams(_run(lm_big, submits))
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER)
    for s in submits:
        eng.submit(**s)
    for _ in range(6):
        eng.step_block()
    snap = json.loads(json.dumps(eng.snapshot()))
    assert snap["config"]["host_tier_pages"] == TIER
    assert "tier" not in json.dumps(snap["requests"])   # no tier content
    pre = _streams(eng)
    restored = ServeEngine.from_snapshot(lm_small, snap)
    assert restored.host_tier_pages == TIER
    assert restored.session.paged.tier_pages() == 0     # starts empty
    restored.run()
    merged = dict(pre)
    merged.update(_streams(restored))
    assert merged == oracle


# ------------------------------------------------- tier fault seam / ladder

def test_restore_failure_degrades_to_reprefill_exact(stack):
    """Every tier restore FAILS (seeded): admission falls back to
    re-prefilling the suffix — streams still equal the oracle, failures
    are counted, and nothing is shed that the untiered run served."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    oracle = _streams(_run(lm_big, submits))
    eng = _run(lm_small, submits, host_tier_pages=TIER,
               faults=FaultPlan(seed=3, tier_restore_fail_prob=1.0))
    pkv = eng.session.paged
    assert eng._injector.stats["tier_restore_faults"] > 0
    assert pkv.stats["tier_restore_failures"] > 0
    assert pkv.stats["tier_restored_pages"] == 0
    assert len(eng.rejected) == 0
    assert _streams(eng) == oracle


def test_corrupted_tier_bytes_caught_by_checksum_exact(stack):
    """Corrupted host-tier bytes are CAUGHT by the per-page checksum and
    the copy dropped — the admission re-prefills; never a wrong token."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    oracle = _streams(_run(lm_big, submits))
    eng = _run(lm_small, submits, host_tier_pages=TIER,
               faults=FaultPlan(seed=7, tier_corrupt_prob=1.0))
    assert eng._injector.stats["tier_corruptions"] > 0
    assert eng.session.paged.tier.stats["checksum_failures"] > 0
    assert _streams(eng) == oracle


def test_tier_fault_plan_replayed_twice_identical(stack):
    """Determinism gate for the new seam: the same plan over the same
    trace makes identical decisions — streams, engine stats, injector
    stats, and tier stats all match."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    runs = []
    for _ in range(2):
        eng = _run(lm_small, submits, host_tier_pages=TIER,
                   faults=FaultPlan(seed=11, tier_restore_fail_prob=0.4,
                                    tier_corrupt_prob=0.3))
        runs.append((_streams(eng), dict(eng.stats),
                     dict(eng._injector.stats),
                     dict(eng.session.paged.stats)))
    assert runs[0] == runs[1]


def test_corrupt_device_page_repaired_from_inclusive_tier_copy(stack):
    """A corrupted DEVICE page whose radix entry keeps an inclusive host
    copy is repaired IN PLACE: no replay, no subtree invalidation — and
    the LIVE stream reading through that page stays bit-identical (the
    repair provably rewrote the bytes before the next block)."""
    cfg, params, lm_big, lm_small = stack
    a = _family(9, 2)
    golden = _streams(_run(lm_big, [dict(prompt=a[0], max_new_tokens=6),
                                    dict(prompt=a[1], max_new_tokens=12)]))
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER)
    r0 = eng.submit(a[0], 6)
    eng.run()
    pkv = eng.session.paged
    pkv.prefix.spill(10 ** 6)          # prefix now host-resident only
    r1 = eng.submit(a[1], 12)          # restore -> inclusive copies exist
    eng.step_block()
    assert pkv.stats["tier_restored_pages"] > 0
    victims = [n.page for n in pkv.prefix._iter_nodes()
               if n.page >= 0 and n.tier_id is not None]
    assert victims, "expected device-resident pages with tier copies"
    eng.inject_page_corruption(victims[:1])
    assert eng.stats["tier_page_repairs"] == 1
    assert eng.stats["corrupt_page_replays"] == 0
    eng.run()
    assert _streams(eng) == {r0: golden[0], r1: golden[1]}


def test_chaos_storm_tiered_allocator_and_tier_drain_to_zero(stack):
    """All four engine seams armed (pool storms, dispatch failures, page
    corruption, tier faults) on a tiered small-pool engine: streams equal
    the no-fault infinite-pool oracle, corrupted device pages with tier
    copies restore from the tier, and after the trace BOTH the allocator
    and the tier drain to zero — no leak across spill/restore/abort/replay
    cycles."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    oracle = _streams(_run(lm_big, submits, prefill_chunk_tokens=5))
    eng = _run(lm_small, submits, prefill_chunk_tokens=5,
               host_tier_pages=TIER, dispatch_retries=8,
               dispatch_backoff_s=0.0,
               faults=FaultPlan(seed=1, pool_exhaust_prob=0.3,
                                pool_storm_len=2, dispatch_fail_prob=0.25,
                                dispatch_max_failures=2,
                                corrupt_page_prob=0.3,
                                tier_restore_fail_prob=0.15,
                                tier_corrupt_prob=0.1))
    assert not eng.queue and not eng._prefilling and not eng._replay_q
    inj = eng._injector.stats
    assert inj["alloc_faults"] > 0 and inj["pages_corrupted"] > 0
    assert _streams(eng) == oracle
    pkv = eng.session.paged
    _drain_all(pkv)
    assert pkv.allocator.in_use() == 0
    assert pkv.tier_pages() == 0 and pkv.tier_bytes() == 0


# ------------------------------------------------- index / scheduler units

def test_peek_reports_tiered_hit_without_restore_or_lru_touch(stack):
    """ISSUE 8 satellite: ``peek``/``prefix_peek`` see tiered entries (the
    Router's affinity probe must prefer a replica whose TIER holds the
    prefix) without touching the LRU clock, taking holds, or restoring."""
    cfg, params, lm_big, lm_small = stack
    a = _family(13, 1, tail=8)
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER)
    eng.submit(a[0], 4)
    eng.run()
    pkv = eng.session.paged
    pkv.prefix.spill(10 ** 6)
    stamps = {id(n): n.last_used for n in pkv.prefix._iter_nodes()}
    pages = pkv.prefix.peek(a[0].tolist())
    assert len(pages) >= 2 and all(p == -1 for p in pages[:2])
    assert pkv.prefix_peek(a[0].tolist()) >= 2 * PAGE
    # read-only: no restore ran, no LRU stamp moved, no hold taken
    assert pkv.stats["tier_restored_pages"] == 0
    assert {id(n): n.last_used
            for n in pkv.prefix._iter_nodes()} == stamps
    assert pkv.allocator.in_use() == 0


def test_evictable_spillable_reclaimable_counts(stack):
    """``evictable_pages`` counts device pages only (tiered entries are
    transparent, never pinning an ancestor); ``spillable_pages`` counts
    every cache-only device page; ``reclaimable_pages`` picks the ladder's
    reach (spillable with a tier, evictable without)."""
    cfg, params, lm_big, lm_small = stack
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER)
    a = _family(15, 1, tail=8)
    eng.submit(a[0], 4)
    eng.run()
    pkv = eng.session.paged
    dev = sum(1 for n in pkv.prefix._iter_nodes() if n.page >= 0)
    assert dev >= 4
    assert pkv.prefix.evictable_pages() == dev
    assert pkv.prefix.spillable_pages() == dev
    assert pkv.prefix.reclaimable_pages() == dev
    # spill half: tiered entries leave BOTH counts (no device page) but
    # stay transparent — the remaining device pages are all still reachable
    pkv.prefix.spill(2)
    assert pkv.prefix.evictable_pages() == dev - 2
    assert pkv.prefix.spillable_pages() == dev - 2
    # untiered engine: reclaimable falls back to evictable
    eng_u = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42))
    eng_u.submit(a[0], 4)
    eng_u.run()
    pkv_u = eng_u.session.paged
    assert pkv_u.prefix.spillable_pages() == 0
    assert (pkv_u.prefix.reclaimable_pages()
            == pkv_u.prefix.evictable_pages() > 0)


def test_pool_retry_after_spill_vs_oldest_stream_branches(stack):
    """ISSUE 8 satellite: when a SPILL could free enough pages for the
    shed request, ``retry_after_blocks`` reflects spill latency (1 block);
    when the pool is pinned by live streams, it falls back to the oldest
    decoding stream's remaining budget."""
    cfg, params, lm_big, lm_small = stack
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER)
    a = _family(17, 2, tail=8)
    # phase 1: one live stream pins the WHOLE 10-page capacity
    # (16 prompt + 20 budget + K over 4/page = 10 pages): nothing is
    # spillable, so the estimate reads the oldest stream's remaining budget
    r1 = eng.submit(a[0], 20)
    eng.step_block()
    from neuronx_distributed_tpu.inference.engine import Request
    probe = Request(request_id=999, prompt=a[1], max_new_tokens=8)
    assert eng.session.paged.prefix.spillable_pages() == 0
    expect = -(-(20 - len(eng._out[r1])) // K)
    assert eng._pool_retry_after(probe) == max(1, expect) > 1
    # phase 2: the stream retires; its pages are cache-only (spillable),
    # so the same probe's shortfall is one spill away: retry after 1 block
    eng.run()
    assert eng.session.paged.prefix.spillable_pages() > 0
    assert eng._pool_retry_after(probe) == 1
    # untiered contrast: same drained state, no tier -> oldest-stream path
    eng_u = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42))
    eng_u.submit(a[0], 20)
    eng_u.step_block()
    assert eng_u._pool_retry_after(probe) == max(
        1, -(-(20 - len(eng_u._out[0])) // K))


def test_register_readopts_tiered_entry(stack):
    """A re-prefill over a TIERED path re-adopts the freshly written device
    pages into the trie (tier copy kept), so the next hit skips both the
    restore and the re-prefill."""
    cfg, params, lm_big, lm_small = stack
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER,
                      faults=FaultPlan(seed=19, tier_restore_fail_prob=0.0))
    a = _family(21, 2)
    eng.submit(a[0], 4)
    eng.run()
    pkv = eng.session.paged
    pkv.prefix.spill(10 ** 6)
    # break the restore path for ONE admission: hook forces a failure, the
    # entries' subtrees drop, and the admission re-prefills + re-registers
    calls = {"n": 0}

    def fail_once():
        calls["n"] += 1
        return "fail" if calls["n"] == 1 else None

    pkv.tier.fault_hook = fail_once
    eng.submit(a[1], 4)
    eng.run()
    assert pkv.stats["tier_restore_failures"] >= 1
    # the re-prefilled prefix is device-resident again (re-registered)
    assert pkv.prefix_peek(a[1].tolist()) >= 2 * PAGE


# ------------------------------------------------------- router + validation

def test_router_affinity_prefers_replica_with_tiered_prefix(stack):
    """Placement treats a TIERED prefix as hot: after replica 0's prefix
    spills to its host tier, a prefix-sharing request still routes to
    replica 0 (peek sees the tiered entries) and restores there."""
    from neuronx_distributed_tpu.inference.router import Router

    cfg, params, lm_big, lm_small = stack
    a = _family(23, 2)
    router = Router(lm_small, 2, block_steps=K, rng=jax.random.key(0),
                    host_tier_pages=TIER)
    router.submit(a[0], 4)
    router.run()
    pkv0 = router.engines[0].session.paged
    assert pkv0.prefix_peek(a[1].tolist()) >= 2 * PAGE
    pkv0.prefix.spill(10 ** 6)
    assert pkv0.prefix_peek(a[1].tolist()) >= 2 * PAGE   # tiered hit
    router.submit(a[1], 4)
    router.run()
    assert router.stats["affinity_placements"] >= 1
    assert pkv0.stats["tier_restored_pages"] > 0
    assert len(router.completed) == 2


def test_tier_knob_validation(stack):
    cfg, params, lm_big, lm_small = stack
    with pytest.raises(ValueError, match="host_tier_pages"):
        ServeEngine(lm_small, block_steps=K, host_tier_pages=-1)
    cfg_ = LlamaConfig(**TINY)
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="paged CausalLM"):
        lm_c = CausalLM(cfg_, params, LlamaForCausalLM, buckets=(8, 16),
                        max_batch=3)
        ServeEngine(lm_c, block_steps=K, host_tier_pages=8)
    with pytest.raises(ValueError, match="tier_restore_fail_prob"):
        FaultPlan(tier_restore_fail_prob=1.5)
    with pytest.raises(ValueError, match="<= 1"):
        FaultPlan(tier_restore_fail_prob=0.7, tier_corrupt_prob=0.7)
    with pytest.raises(ValueError, match=">= 1 page"):
        HostPageTier(0)


def test_host_page_tier_store_checksum_and_lru():
    """Unit: put/get round-trips bytes, a garbled entry raises
    :class:`TierCorruption` and is dropped, and capacity overflow LRU-drops
    the coldest entry (reported to the caller)."""
    tier = HostPageTier(2)
    d1 = {"k": np.arange(8, dtype=np.float32)}
    t1, ev = tier.put(d1)
    assert ev == [] and len(tier) == 1
    got = tier.get(t1)
    assert np.array_equal(got["k"], d1["k"])
    # physical garble -> checksum catches, entry dropped
    tier._entries[t1]["data"]["k"].view(np.uint8)[0] ^= 0xFF
    with pytest.raises(TierCorruption):
        tier.get(t1)
    assert len(tier) == 0
    # LRU overflow: oldest entry evicted and returned
    ta, _ = tier.put(d1)
    tb, _ = tier.put(d1)
    tier.get(ta)                       # ta now warmer than tb
    tc, dropped = tier.put(d1)
    assert dropped == [tb] and len(tier) == 2
    assert tier.bytes_used() == 2 * d1["k"].nbytes


# ------------------------------------------- request_timeline (ISSUE 9)

def test_request_timeline_covers_tier_restore_lane(stack):
    """ISSUE 9 satellite: the PR 8 tier-restore lane is visible from the
    REQUEST's own timeline — the admission that restored spilled prefix
    pages carries a ``tier_restore`` instant (page count included), the
    cache-lane ``tier:*`` instants are block-stamped, and the attribution
    layer picks the restore up as an annotation while its phase sums still
    close exactly."""
    cfg, params, lm_big, lm_small = stack
    submits = _pressure_submits()
    eng = ServeEngine(lm_small, block_steps=K, rng=jax.random.key(42),
                      host_tier_pages=TIER, trace=True)
    for s in submits:
        eng.submit(**s)
    eng.run(max_blocks=300)
    pkv = eng.session.paged
    assert pkv.stats["tier_restored_pages"] > 0
    # the A-family return (last submit) is the restore hit
    rid = len(submits) - 1
    tl = eng.request_timeline(rid)
    names = [e["name"] for e in tl]
    assert names[0] == "submit" and names[-1] == "retire"
    assert "tier_restore" in names, names
    ev = next(e for e in tl if e["name"] == "tier_restore")
    assert ev["args"]["pages"] > 0 and ev["block"] is not None
    # cache-lane tier events now ride the virtual block clock too
    tier_evs = [e for e in eng.tracer.events(lane_group="cache")
                if e["name"].startswith("tier:")]
    assert tier_evs and all(e["block"] is not None for e in tier_evs)
    assert any(e["name"] == "tier:restore" for e in tier_evs)
    # attribution sees the restore and the invariant still closes
    att = eng.request_attribution(rid)
    assert att["annotations"]["tier_restored_pages"] > 0
    assert sum(att["phases_blocks"].values()) == att["e2e_blocks"]
    _drain_all(pkv)
    assert pkv.allocator.in_use() == 0
