"""Multi-LoRA serving gates (ISSUE 10 tentpole).

The adapter pool's whole value is that per-request adapters change NOTHING
about the tokens a given adapter produces: every test here pins the
exactness oracle — a request served under adapter X out of a MIXED pool
(other adapters decoding in neighbouring slots, load/evict churn mid-trace)
emits the bit-identical stream a solo ``generate`` on X's
``export_merged_hf`` merged-and-reloaded model emits — across fused vs
stepwise engines and paged vs contiguous caches, greedy and sampled. Plus
the compiled-program contract (zero recompiles when the adapter mix
changes: the pool is an input, not a constant), the structured
``adapter_pool_exhausted`` rejection, the seeded ``adapter`` fault seam
(replay-identical, never a wrong-adapter token), snapshot/restore, and the
Router's adapter-affinity / drain-pin-migration satellites.

Tier-1 cost discipline: ONE module-scoped lora CausalLM (+ one paged twin
and two max_batch-1 merged-golden lms) serves every test; block_steps=4
throughout so each lm compiles a single session program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.inference.adapters import AdapterPoolExhausted
from neuronx_distributed_tpu.inference.faults import FaultPlan
from neuronx_distributed_tpu.inference.router import Router
from neuronx_distributed_tpu.lora import LoraConfig, init_lora
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from tests import tiny

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
RANK, SLOTS = 4, 3          # identity + 2 resident: 3 adapters MUST churn
ACFG = LoraConfig(r=RANK, lora_alpha=8.0)


@pytest.fixture(scope="module")
def base():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return cfg, params


def _mk_adapter(params, i):
    """init_lora tree with a nonzero, adapter-distinct B (B=0 would make
    every adapter the identity and the oracle vacuous)."""
    ad = init_lora(params, ACFG, jax.random.key(10 + i))
    return {k: {"lora_a": v["lora_a"],
                "lora_b": 0.05 * jax.random.normal(
                    jax.random.fold_in(jax.random.key(20 + i), j),
                    v["lora_b"].shape, jnp.float32)}
            for j, (k, v) in enumerate(sorted(ad.items()))}


@pytest.fixture(scope="module")
def adapters(base):
    _cfg, params = base
    return {f"a{i}": _mk_adapter(params, i) for i in range(3)}


@pytest.fixture(scope="module")
def lm(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, lora_rank=RANK, lora_slots=SLOTS).compile()


@pytest.fixture(scope="module")
def lm_paged(base):
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=4, lora_rank=RANK,
                    lora_slots=SLOTS).compile()


@pytest.fixture(scope="module")
def merged_lms(base, adapters, tmp_path_factory):
    """The ISSUE's oracle models: each adapter merged via export_merged_hf,
    written as a standard HF checkpoint, reloaded through the converter —
    the zero-LoRA-machinery serving path the pooled path must match
    bit-for-bit."""
    from neuronx_distributed_tpu.converters.hf_llama import (
        hf_to_nxd_llama,
        load_hf_safetensors,
    )
    from neuronx_distributed_tpu.lora import export_merged_hf

    cfg, params = base
    out = {}
    for name in ("a0", "a1"):
        path = export_merged_hf(
            params, adapters[name], ACFG, cfg,
            str(tmp_path_factory.mktemp(f"hf_{name}")))
        reloaded = hf_to_nxd_llama(load_hf_safetensors(path), cfg,
                                   dtype=jnp.float32)
        out[name] = CausalLM(cfg, reloaded, LlamaForCausalLM,
                             buckets=(8, 16), max_batch=1).compile()
    return out


def _prompts(n, s=8, seed=5):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


P = _prompts(4)

# the canonical mixed-pool schedule: two adapters decode side by side with a
# base request, then a THIRD adapter arrives after a slot freed — its load
# must evict a cold adapter mid-trace (SLOTS holds identity + 2)
SUBMITS = [dict(prompt=P[0], max_new_tokens=6, adapter="a0"),
           dict(prompt=P[1], max_new_tokens=5, adapter="a1",
                arrival_block=1),
           dict(prompt=P[2], max_new_tokens=6),
           dict(prompt=P[3], max_new_tokens=5, adapter="a2", arrival_block=6,
                sampler=Sampler(temperature=0.9))]


def _run(lm_, fused, reg, submits=SUBMITS, faults=None, rng_seed=42, **kw):
    eng = ServeEngine(lm_, block_steps=K, fused=fused,
                      rng=jax.random.key(rng_seed), faults=faults, **kw)
    _register(eng, reg)
    return eng, *_submit_and_run(eng, submits)


def _submit_and_run(eng, submits):
    rids = [eng.submit(**kw) for kw in submits]
    comps = {c.request_id: c for c in eng.run()}
    return rids, {r: comps[r].tokens.tolist() for r in rids if r in comps}


def _register(target, adapters):
    for name, ad in adapters.items():
        target.register_adapter(name, ad, ACFG)


def test_adapter_streams_match_merged_export_oracle(lm, lm_paged, adapters,
                                                    merged_lms):
    """THE oracle: per-request adapter streams out of a mixed pool with
    mid-trace load/evict churn, bit-identical across fused/stepwise ×
    paged/contiguous (greedy AND sampled), with every greedy adapter stream
    equal to solo generate on that adapter's merged-export model and the
    base request equal to plain generate (its slot-0 identity row is
    unperturbed by the adapter rows decoding next to it)."""
    results = {}
    engines = {}
    for tag, lm_ in (("contig", lm), ("paged", lm_paged)):
        for fused in (True, False):
            eng = ServeEngine(lm_, block_steps=K, fused=fused,
                              rng=jax.random.key(42))
            _register(eng, adapters)
            rids, res = _submit_and_run(eng, SUBMITS)
            results[(tag, fused)] = res
            engines[(tag, fused)] = eng
    first = results[("contig", True)]
    for key, res in results.items():
        assert res == first, key
    # mid-trace churn really happened: a2's load evicted a cold adapter
    for eng in engines.values():
        assert eng.session.adapters.stats["evictions"] >= 1
        assert eng.stats["adapter_rejects"] == 0
    # greedy adapter streams == solo merged-export generate
    for i, name in ((0, "a0"), (1, "a1")):
        g = merged_lms[name].generate(
            P[i: i + 1], max_new_tokens=SUBMITS[i]["max_new_tokens"])
        assert first[i] == g.tokens[0].tolist(), name
    # the base request rode the identity slot: == plain generate on the lm
    g = lm.generate(P[2:3], max_new_tokens=6)
    assert first[2] == g.tokens[0].tolist()
    # the sampled a2 stream actually decoded its budget
    assert len(first[3]) == 5


def test_chunked_prefill_under_adapter_matches_merged(lm, adapters,
                                                      merged_lms):
    """Chunked admission must prefill under the request's adapter (the KV
    it writes is adapter-specific): a 16-token prompt prefilled 4 tokens
    per round streams bit-identical to the one-shot merged-export
    generate."""
    prompt = _prompts(1, s=16, seed=9)
    eng = ServeEngine(lm, block_steps=K, prefill_chunk_tokens=4,
                      rng=jax.random.key(42))
    _register(eng, adapters)
    rid = eng.submit(prompt[0], 6, adapter="a0")
    comps = {c.request_id: c for c in eng.run()}
    assert eng.stats["chunk_program_calls"] >= 4
    g = merged_lms["a0"].generate(prompt, max_new_tokens=6)
    assert comps[rid].tokens.tolist() == g.tokens[0].tolist()


def test_zero_recompiles_when_adapter_mix_changes(lm, adapters):
    """Compiled-program cache identity: the pool rides every program as an
    INPUT, so a different adapter mix (different residency, different
    churn) compiles nothing new."""
    # warm every program the schedules below can touch
    _run(lm, True, adapters)
    _run(lm, False, adapters)
    before = dict(lm.compile_ms)
    alt = [dict(prompt=P[0], max_new_tokens=4, adapter="a2"),
           dict(prompt=P[1], max_new_tokens=4, adapter="a1",
                arrival_block=1),
           dict(prompt=P[2], max_new_tokens=4, adapter="a0",
                arrival_block=5)]
    for fused in (True, False):
        eng, _, _ = _run(lm, fused, adapters, submits=alt, rng_seed=1)
        assert eng.session.adapters.stats["loads"] >= 2
    assert dict(lm.compile_ms) == before, (
        set(lm.compile_ms) - set(before))


def test_adapter_pool_exhausted_structured_reject(lm, adapters):
    """Pool full and nothing evictable (every slot pinned by a live
    stream): the overflow admission is shed with
    Rejected(reason='adapter_pool_exhausted') and a retry-after; the same
    request admits cleanly once pins return."""
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42))
    _register(eng, adapters)
    rids = [eng.submit(P[i], 4, adapter=f"a{i}") for i in range(3)]
    comps = eng.run()
    assert len(comps) == 2
    assert len(eng.rejected) == 1
    rej = eng.rejected[0]
    assert rej.reason == "adapter_pool_exhausted"
    assert rej.retry_after_blocks >= 1
    assert eng.stats["adapter_rejects"] == 1
    victim = next(i for i in range(3) if rids[i] == rej.request_id)
    # pins returned: the shed adapter now loads (evicting LRU) and serves
    eng2 = ServeEngine(lm, block_steps=K, rng=jax.random.key(42))
    _register(eng2, adapters)
    rid = eng2.submit(P[victim], 4, adapter=f"a{victim}")
    comps2 = {c.request_id: c for c in eng2.run()}
    assert len(comps2[rid].tokens) == 4


def test_adapter_fault_seam_chaos_replay_identical(lm, adapters):
    """The seeded ``adapter`` seam: injected load failures requeue-and-
    retry, corrupted device bytes are caught by checksum and repaired from
    the registry — streams stay bit-identical to the no-fault oracle
    (NEVER a silent wrong-adapter token), and the same plan replayed makes
    the same decisions in the same order."""
    _, _, oracle = _run(lm, True, adapters)
    plan = dict(seed=0, adapter_load_fail_prob=0.3, adapter_corrupt_prob=0.3)
    runs = []
    for _ in range(2):
        eng, _, res = _run(lm, True, adapters, faults=FaultPlan(**plan))
        runs.append((res, dict(eng._injector.stats),
                     eng.session.adapters.stats["repairs"],
                     int(eng.stats["adapter_load_retries"])))
    assert runs[0] == runs[1], "fault plan must replay identically"
    res, istats, repairs, retries = runs[0]
    assert res == oracle
    assert istats["adapter_load_faults"] >= 1 and retries >= 1
    assert istats["adapter_corruptions"] >= 1 and repairs >= 1
    # stepwise under the same plan: same admission schedule, same streams
    _, _, res_s = _run(lm, False, adapters, faults=FaultPlan(**plan))
    assert res_s == oracle


def test_snapshot_restore_resumes_adapter_streams(lm, adapters):
    """Crash recovery with adapters: the snapshot carries adapter NAMES
    (weights die with the process, like device pages); from_snapshot
    re-registers them and the replayed streams resume bit-identical."""
    _, _, oracle = _run(lm, True, adapters)
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(42))
    _register(eng, adapters)
    rids = [eng.submit(**kw) for kw in SUBMITS]
    eng.run(max_blocks=2)
    snap = eng.snapshot()
    reg = {name: (ad, ACFG) for name, ad in adapters.items()}
    eng2 = ServeEngine.from_snapshot(lm, snap, adapters=reg)
    done = {c.request_id: c.tokens.tolist() for c in eng.completed}
    for c in eng2.run():
        done[c.request_id] = (done.get(c.request_id, []) + c.tokens.tolist()
                              if c.request_id in done else c.tokens.tolist())
    # restored streams replayed delivered tokens too — compare full streams
    combined = {}
    for rid in rids:
        pre = next((c.tokens.tolist() for c in eng.completed
                    if c.request_id == rid), None)
        post = next((c.tokens.tolist() for c in eng2.completed
                     if c.request_id == rid), None)
        combined[rid] = pre if pre is not None else post
    assert combined == oracle


def test_router_adapter_affinity_and_replica_states(lm, adapters):
    """Router satellite: placement prefers the replica whose pool already
    holds the request's adapter (the prefix-affinity economics applied to
    adapter loads), and replica_states surfaces residency."""
    router = Router(lm, 2, placement="least_loaded", block_steps=K,
                    rng=jax.random.key(42))
    router.register_adapter("a0", adapters["a0"], ACFG)
    r0 = router.submit(P[0], 4, adapter="a0")
    router.run(max_blocks=4)
    states = router.replica_states()
    homes = [s["replica"] for s in states if s["adapters_resident"]]
    assert len(homes) == 1
    assert states[homes[0]]["adapters_resident"] == ["a0"]
    # a later a0 request with BOTH replicas idle must follow the residency
    r1 = router.submit(P[1], 4, adapter="a0", arrival_block=router.blocks)
    router.run()
    placed = {c.request_id: i for i, eng in enumerate(router.engines)
              for c in eng.completed}
    assert placed[r0] == placed[r1] == homes[0]
    assert router.engines[homes[0]].session.adapters.stats["loads"] == 1


def test_router_drain_migrates_adapter_pins(lm, adapters):
    """Drain satellite: queued adapter work migrates to a peer WITH its
    pin — the source replica ends unpinned (only the residency hold), the
    destination loads the adapter, and zero tokens are lost."""
    router = Router(lm, 2, placement="least_loaded", block_steps=K,
                    rng=jax.random.key(1))
    router.register_adapter("a0", adapters["a0"], ACFG)
    rA = router.submit(P[0], 12, adapter="a0")
    router.step_block()
    src = next(i for i, eng in enumerate(router.engines)
               if any(r is not None for r in eng.slots))
    rB = router.submit(P[1], 6, adapter="a0",
                       arrival_block=router.blocks + 1)
    router.drain(src)
    comps = {c.request_id: c for c in router.run()}
    assert len(comps[rA].tokens) == 12 and len(comps[rB].tokens) == 6
    dst = 1 - src
    assert router.engines[dst].session.adapters.is_resident("a0")
    assert router.engines[src].session.adapters.pinned("a0") == 0
    assert src in router.snapshots   # drained replica parked with snapshot
    # both replicas' streams came from the SAME request keys: rB equals its
    # solo run no matter where it decoded
    eng = ServeEngine(lm, block_steps=K, rng=jax.random.key(1))
    _register(eng, adapters)
    solo = eng.submit(P[1], 6, adapter="a0", request_id=rB)
    solo_comps = {c.request_id: c for c in eng.run()}
    assert comps[rB].tokens.tolist() == solo_comps[solo].tokens.tolist()


def test_radix_prefix_reuse_is_adapter_namespaced(lm_paged, adapters):
    """ISSUE 12 regression pin: a prefix's KV is a function of (tokens,
    adapter) — before the namespaced radix, a page-aligned prefix built by
    BASE-model traffic was silently reused for an adapter-pinned request
    (and across adapters), serving wrong tokens. Now: cross-adapter
    admissions on the same prompt prefix never match (each re-prefills and
    streams exactly like its solo run), while SAME-adapter traffic keeps
    full radix reuse (the prefix-hit economics survive the fix)."""
    prefix = _prompts(1, s=12, seed=31)[0]
    tails = _prompts(3, s=4, seed=33)

    def solo(adapter, rid, tail):
        eng = ServeEngine(lm_paged, block_steps=K, rng=jax.random.key(7))
        _register(eng, adapters)
        eng.submit(np.concatenate([prefix, tail]), 6, adapter=adapter,
                   request_id=rid)
        comps = eng.run()
        return comps[0].tokens.tolist()

    eng = ServeEngine(lm_paged, block_steps=K, rng=jax.random.key(7))
    _register(eng, adapters)
    pkv = eng.session.paged
    # 1) base-model request plants the prefix path
    r0 = eng.submit(np.concatenate([prefix, tails[0]]), 6)
    eng.run()
    assert pkv.stats["prefix_hits"] == 0
    # 2) a0 on the SAME prefix: must NOT hit the base-model path — and the
    # stream equals a0's solo run on a cold engine
    r1 = eng.submit(np.concatenate([prefix, tails[1]]), 6, adapter="a0")
    comps = {c.request_id: c for c in eng.completed + eng.run()}
    assert pkv.stats["prefix_hits"] == 0, \
        "cross-adapter prefix reuse would serve wrong tokens"
    assert comps[r1].tokens.tolist() == solo("a0", r1, tails[1])
    # 3) a0 AGAIN: same-namespace reuse works (hit), stream still exact
    r2 = eng.submit(np.concatenate([prefix, tails[2]]), 6, adapter="a0")
    comps = {c.request_id: c for c in eng.completed + eng.run()}
    assert pkv.stats["prefix_hits"] == 1
    assert pkv.stats["prefix_hit_tokens"] > 0
    assert comps[r2].tokens.tolist() == solo("a0", r2, tails[2])
    # the affinity probe answers per namespace too
    full = np.concatenate([prefix, tails[2]]).tolist()
    assert pkv.prefix_peek(full, ns="a0") > 0
    assert pkv.prefix_peek(full, ns="a1") == 0
    assert pkv.prefix_peek(full) > 0      # the base path is still cached
