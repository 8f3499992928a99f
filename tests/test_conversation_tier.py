"""Persistent conversation tier (ISSUE 20 tentpole gates).

Three acceptance surfaces:

* the STORE — durable park/resume round-trips with checkpoint-integrity
  discipline: shards → sha256 manifest → done marker, each write atomic,
  so a torn park (crash before the marker) is quarantined on the next
  read/sweep and NEVER half-trusted; corrupt-at-rest bytes are caught by
  sha256/crc and quarantined; a state-only park (KV write failed) still
  lands the request state durably;
* the EXACTNESS ORACLE — park → full eviction (0 device pages, 0 host
  pages) → resume produces token streams bit-identical to a never-parked
  run, across fused/stepwise × greedy/sampled × grammar × adapter on the
  paged pool, across a process restart (fresh engine, same store), and
  across replicas (fleet-global store: a conversation parked by a
  since-drained replica resumes on a survivor);
* the DEGRADATION LADDER — every injected park fault
  (``park_write_fail_prob`` → state-only, ``park_read_fail_prob`` → read
  fault, ``park_corrupt_prob`` → at-rest flip) ends in the replay path,
  cold-identical by the rng contract: a park fault is a latency event,
  never a wrong token. The SIGKILL test makes the crash REAL: a child
  process dies by signal 9 mid-park and the parent proves the torn
  manifest quarantines while the clean park resumes bit-identical.

Tier-1 cost discipline: one module-scoped paged lm carrying BOTH the
adapter pool and the grammar pool (identity slots keep base requests
bit-identical — the multilora/structured suites' proven property), so the
whole matrix shares one compile.
"""

import os
import signal
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import (
    CausalLM,
    Rejected,
    Sampler,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.conversation_tier import (
    ConversationParkStore,
    ParkIntegrityError,
    ParkReadFailed,
)
from neuronx_distributed_tpu.inference.faults import FaultInjector, FaultPlan
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
PAGE = 4
RANK, ASLOTS = 4, 3
GSLOTS, GSTATES = 3, 48
ACFG = LoraConfig(r=RANK, lora_alpha=8.0)


@pytest.fixture(scope="module")
def base():
    cfg = LlamaConfig(**TINY)
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    return cfg, params


@pytest.fixture(scope="module")
def lm(base):
    """One paged lm with adapter AND grammar pools — the whole matrix
    shares one compile; identity slots keep plain requests base-exact."""
    cfg, params = base
    return CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3, page_size=PAGE, lora_rank=RANK,
                    lora_slots=ASLOTS, grammar_slots=GSLOTS,
                    grammar_states=GSTATES).compile()


@pytest.fixture(scope="module")
def adapter(base):
    _cfg, params = base
    ad = init_lora(params, ACFG, jax.random.key(10))
    return {k: {"lora_a": v["lora_a"],
                "lora_b": 0.05 * jax.random.normal(
                    jax.random.fold_in(jax.random.key(20), j),
                    v["lora_b"].shape, jnp.float32)}
            for j, (k, v) in enumerate(sorted(ad.items()))}


def _prompts(n, s=8, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


P = _prompts(4)

# greedy + sampled + grammar-constrained + adapter — the paged matrix in
# one pool (max_batch=3 forces the third submit to queue behind a slot)
MATRIX = [dict(prompt=P[0], max_new_tokens=12, adapter="a0"),
          dict(prompt=P[1], max_new_tokens=10, grammar="gab",
               sampler=Sampler(temperature=1.3)),
          dict(prompt=P[2], max_new_tokens=8, arrival_block=1,
               sampler=Sampler(temperature=0.8))]


def _mk_engine(lm_, fused=True, adapter_reg=None, **kw):
    eng = ServeEngine(lm_, block_steps=K, fused=fused,
                      rng=jax.random.key(42), **kw)
    if adapter_reg is not None:
        eng.register_adapter("a0", adapter_reg, ACFG)
    eng.register_grammar("gab", regex="a[ab]*b")
    return eng


def _streams(eng):
    return {c.request_id: c.tokens.tolist() for c in eng.completed}


def _oracle(lm_, submits, fused=True, adapter_reg=None, **kw):
    eng = _mk_engine(lm_, fused=fused, adapter_reg=adapter_reg, **kw)
    for s in submits:
        eng.submit(**s)
    eng.run()
    return _streams(eng)


def _active_rids(eng):
    return sorted(r.request_id for s, r in enumerate(eng.slots)
                  if r is not None and not eng._done[s])


# ------------------------------------------------------------ store units

def _payload(i, pages=1):
    """One page's leaf dict, adapter-distinct content (two leaves per
    layer like the real cache tree)."""
    rng = np.random.default_rng(100 + i)
    return {f"layer{l}/{kv}": rng.standard_normal(
        (2, PAGE, 2, 4)).astype(np.float32)
        for l in range(2) for kv in ("k", "v")}


_STATE0 = {"prompt": [5, 6, 7], "generated": [9, 11], "length": 4,
           "parked_block": 3, "rng_key": [1, 2]}


def test_store_roundtrip_and_remove(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    pays = [_payload(0), _payload(1)]
    mid, verdict = store.park(7, _STATE0, pays, tp_degree=2,
                              page_dtype="int8")
    assert verdict is None and store.contains(7)
    assert store.list_parked() == [7]
    assert store.parked_bytes(7) > 0
    back = store.load(7)
    assert back.request_id == 7 and back.manifest_id == mid
    assert back.state == _STATE0
    assert back.tp_degree == 2 and back.page_dtype == "int8"
    assert len(back.payloads) == 2
    for got, want in zip(back.payloads, pays):
        assert sorted(got) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    store.remove(7)
    assert not store.contains(7) and store.list_parked() == []


def test_store_state_only_park(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    store.park(3, _STATE0, None)
    back = store.load(3)
    assert back.payloads is None and back.state == _STATE0
    assert store.manifest(3)["state_only"] is True


def test_store_torn_park_quarantined_state_recoverable(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    store.write_fault_hook = lambda: "torn"
    _mid, verdict = store.park(4, _STATE0, [_payload(0)])
    assert verdict == "torn"
    # a torn park is invisible to every trusting reader...
    assert not store.contains(4) and store.list_parked() == []
    with pytest.raises(ParkIntegrityError):
        store.load(4)
    assert store.stats["quarantined"] == 1
    with pytest.raises(ParkIntegrityError):   # quarantine is sticky
        store.load(4)
    # ...but the state shard verified independently: the middle rung of
    # the degradation ladder still re-prefills bit-identically from it
    assert store.recover_state(4) == _STATE0


def test_store_corrupt_bytes_quarantined(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    store.park(5, _STATE0, [_payload(0)])
    store.read_fault_hook = lambda: "corrupt"
    with pytest.raises(ParkIntegrityError):
        store.load(5)
    assert store.stats["quarantined"] == 1
    store.read_fault_hook = None
    with pytest.raises(ParkIntegrityError):   # poison survives clean reads
        store.load(5)


def test_store_read_fault_leaves_record_intact(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    store.park(6, _STATE0, [_payload(0)])
    store.read_fault_hook = lambda: "fail"
    with pytest.raises(ParkReadFailed):
        store.load(6)
    # transient: NOT quarantined — the retry succeeds untouched
    assert store.stats["quarantined"] == 0 and store.contains(6)
    store.read_fault_hook = None
    assert store.load(6).state == _STATE0


def test_store_sweep_quarantines_torn(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    store.park(1, _STATE0, [_payload(0)])
    store.write_fault_hook = lambda: "torn"
    store.park(2, _STATE0, [_payload(1)])
    store.write_fault_hook = None
    assert store.sweep() == ([1], [2])
    assert store.sweep() == ([1], [])       # idempotent: already poisoned
    assert store.load(1).state == _STATE0


def test_store_repark_replaces_previous_generation(tmp_path):
    store = ConversationParkStore(str(tmp_path / "park"))
    store.park(9, _STATE0, [_payload(0), _payload(1)])
    st2 = dict(_STATE0, generated=[9, 11, 13], length=5)
    store.park(9, st2, [_payload(2)])
    back = store.load(9)
    assert back.state == st2 and len(back.payloads) == 1


def test_park_fault_plan_replay_twice_identical():
    """The chaos contract: the park seam draws from its own named rng
    stream, so the same FaultPlan replayed twice makes IDENTICAL
    park-write and resume-read decisions."""
    plan = FaultPlan(seed=5, park_write_fail_prob=0.5,
                     park_read_fail_prob=0.25, park_corrupt_prob=0.25)
    runs = []
    for _ in range(2):
        inj = FaultInjector(plan)
        runs.append(([inj.on_park_write() for _ in range(24)],
                     [inj.on_park_read() for _ in range(24)]))
    assert runs[0] == runs[1]
    writes, reads = runs[0]
    assert {"fail", "torn"} <= set(writes) and None in writes
    assert {"fail", "corrupt"} <= set(reads) and None in reads


# ------------------------------------------------- engine park / resume

def test_park_requires_paged_lm(base):
    cfg, params = base
    lm_c = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                    max_batch=3)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(lm_c, block_steps=K, rng=jax.random.key(42),
                    park_dir="/tmp/never-created")


def test_park_evicts_device_and_host_pages(lm, adapter, tmp_path):
    """The residency invariant: after park, the conversation holds ZERO
    device pages and ZERO host-tier pages — its only copy is durable."""
    eng = _mk_engine(lm, adapter_reg=adapter,
                     park_dir=str(tmp_path / "park"), host_tier_pages=16)
    rid = eng.submit(P[0], 16)
    eng.step_block()
    eng.step_block()
    pkv = eng.session.paged
    assert pkv.allocator.in_use() > 0
    assert eng.park(rid) == "parked"
    assert pkv.allocator.in_use() == 0
    assert pkv.tier_pages() == 0
    assert all(r is None for r in eng.slots)
    assert eng.stats["parked"] == 1
    assert eng.park_store.contains(rid)
    assert eng.park_store.parked_bytes(rid) > 0
    assert eng.load_summary().parked == 1


@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused", "stepwise"])
def test_park_resume_exact_matrix(lm, adapter, tmp_path, fused):
    """The exactness oracle over the whole matrix in one pool: greedy ×
    sampled × grammar-constrained × adapter streams all park mid-decode,
    vacate the device entirely, resume, and finish bit-identical to the
    never-parked run."""
    oracle = _oracle(lm, MATRIX, fused=fused, adapter_reg=adapter)
    eng = _mk_engine(lm, fused=fused, adapter_reg=adapter,
                     park_dir=str(tmp_path / "park"))
    for s in MATRIX:
        eng.submit(**s)
    eng.step_block()
    eng.step_block()
    rids = _active_rids(eng)
    assert rids, "the workload must still be decoding at the park point"
    for rid in rids:
        assert eng.park(rid) == "parked"
    assert eng.session.paged.allocator.in_use() == 0
    for rid in rids:
        assert eng.submit(resume=rid) == rid
    eng.run()
    assert _streams(eng) == oracle
    assert eng.stats["resumed"] == eng.stats["parked"] == len(rids)
    assert eng.stats["park_replays"] == 0
    assert eng.park_store.list_parked() == []   # records consumed


def test_resume_after_restart_fresh_engine_same_store(lm, adapter,
                                                      tmp_path):
    """Process-death recovery WITHOUT a snapshot: a fresh engine sharing
    only the park directory enumerates and resumes the old process's
    conversations bit-identical (the park record is self-contained)."""
    submits = [dict(prompt=P[0], max_new_tokens=12),
               dict(prompt=P[1], max_new_tokens=10,
                    sampler=Sampler(temperature=0.9))]
    oracle = _oracle(lm, submits, adapter_reg=adapter)
    old = _mk_engine(lm, adapter_reg=adapter,
                     park_dir=str(tmp_path / "park"))
    for s in submits:
        old.submit(**s)
    old.step_block()
    old.step_block()
    rids = _active_rids(old)
    for rid in rids:
        old.park(rid)
    del old                                      # "process death"
    fresh = _mk_engine(lm, adapter_reg=adapter,
                       park_dir=str(tmp_path / "park"))
    assert fresh.parked_ids() == rids            # restart discovery
    for rid in rids:
        assert fresh.submit(resume=rid) == rid
    fresh.run()
    assert _streams(fresh) == {r: oracle[r] for r in rids}
    assert fresh.stats["park_replays"] == 0      # exact, not degraded


@pytest.mark.parametrize("plan", [
    FaultPlan(seed=3, park_write_fail_prob=1.0),
    FaultPlan(seed=3, park_read_fail_prob=1.0),
    FaultPlan(seed=3, park_corrupt_prob=1.0),
], ids=["write_fail", "read_fail", "corrupt"])
def test_park_fault_degradations_cold_identical(lm, adapter, tmp_path,
                                                plan):
    """Every rung of the degradation ladder lands on the replay path and
    the replay is COLD-IDENTICAL: a park fault costs resume latency,
    never a token. write_fail parks state-only (resume re-prefills from
    the durable state); read_fail degrades from the recovered state;
    corrupt quarantines the record and still ends exact."""
    oracle = _oracle(lm, MATRIX, adapter_reg=adapter)
    eng = _mk_engine(lm, adapter_reg=adapter,
                     park_dir=str(tmp_path / "park"), faults=plan)
    for s in MATRIX:
        eng.submit(**s)
    eng.step_block()
    eng.step_block()
    rids = _active_rids(eng)
    for rid in rids:
        assert eng.park(rid) == "parked"   # faults never surface at park
    assert eng.session.paged.allocator.in_use() == 0
    for rid in rids:
        out = eng.submit(resume=rid)
        assert not isinstance(out, Rejected)
    eng.run()
    assert _streams(eng) == oracle
    assert eng.stats["park_replays"] == len(rids)
    if plan.park_write_fail_prob:
        assert eng.park_store.stats["state_only_parks"] > 0
    if plan.park_corrupt_prob:
        assert eng.park_store.stats["quarantined"] == len(rids)


def test_double_resume_rejected(lm, adapter, tmp_path):
    """The durable record is CONSUMED by a successful resume — a second
    resume of the same id cannot replay a stale stream."""
    eng = _mk_engine(lm, adapter_reg=adapter,
                     park_dir=str(tmp_path / "park"))
    rid = eng.submit(P[0], 12)
    eng.step_block()
    eng.park(rid)
    assert eng.submit(resume=rid) == rid
    again = eng.submit(resume=rid)
    assert isinstance(again, Rejected)
    assert again.reason == "park_unresumable"
    eng.run()


def test_idle_autopark_then_resume_exact(lm, adapter, tmp_path):
    """``park_idle_blocks``: the engine parks long-running conversations
    by itself on the virtual block clock (deterministic think-time
    stand-in) and an explicit resume still finishes bit-identical."""
    submits = [dict(prompt=P[0], max_new_tokens=16)]
    oracle = _oracle(lm, submits, adapter_reg=adapter)
    eng = _mk_engine(lm, adapter_reg=adapter,
                     park_dir=str(tmp_path / "park"), park_idle_blocks=2)
    rid = eng.submit(**submits[0])
    eng.run()                                # drains with the stream parked
    assert eng.stats["parked"] >= 1 and eng.parked_ids() == [rid]
    while eng.parked_ids():
        assert eng.submit(resume=rid) == rid
        eng.run()                            # may auto-park again mid-way
    assert _streams(eng) == oracle


# ----------------------------------------------- SIGKILL crash recovery

_CHILD = textwrap.dedent("""\
    import os, signal, sys
    import jax, jax.numpy as jnp, numpy as np
    from flax.core import meta
    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig, LlamaForCausalLM)

    TINY = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
        dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
    )
    cfg = LlamaConfig(**TINY)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = meta.unbox(
        LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), ids))["params"]
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8, 16),
                  max_batch=3, page_size=4).compile()
    eng = ServeEngine(lm, block_steps=4, rng=jax.random.key(42),
                      park_dir=sys.argv[1])
    p = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (4, 8), 1, 127))
    r0 = eng.submit(p[0], 12)
    r1 = eng.submit(p[1], 10)
    eng.step_block()
    eng.step_block()
    eng.park(r0)                    # clean park: shards + manifest + done
    store = eng.park_store
    real_save_text = store.storage.save_text

    def killer(text, path):
        if path.endswith("/done"):
            # the REAL crash-mid-park shape: the process dies by SIGKILL
            # at the exact instant before the done marker lands
            os.kill(os.getpid(), signal.SIGKILL)
        return real_save_text(text, path)

    store.storage.save_text = killer
    eng.park(r1)
    raise SystemExit("unreachable: SIGKILL must have fired")
""")


def test_sigkill_midpark_quarantines_torn_and_resumes_clean(lm, adapter,
                                                            tmp_path):
    """Satellite 3: a child process is ACTUALLY SIGKILLed between its
    manifest write and its done marker. On restart the store sweep
    quarantines the torn park, the clean park resumes bit-identical, and
    even the torn conversation recovers through the state rung (its
    state shard verified) — cold-identical, never a wrong token."""
    park_dir = str(tmp_path / "park")
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))))
    proc = subprocess.run([sys.executable, str(script), park_dir],
                          capture_output=True, text=True, timeout=420,
                          env=env)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    # restart: same prompts/seed the child used, driven by the module lm
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (4, 8), 1, 127))
    submits = [dict(prompt=prompts[0], max_new_tokens=12),
               dict(prompt=prompts[1], max_new_tokens=10)]
    oracle = _oracle(lm, submits, adapter_reg=adapter)
    store = ConversationParkStore(park_dir)
    ok, torn = store.sweep()
    assert ok == [0] and torn == [1]
    eng = _mk_engine(lm, adapter_reg=adapter, park_store=store)
    assert eng.submit(resume=0) == 0      # exact page re-adoption
    out = eng.submit(resume=1)            # torn → state-rung replay
    assert not isinstance(out, Rejected)
    eng.run()
    assert _streams(eng) == oracle
    assert eng.stats["park_replays"] == 1
    assert eng.stats["resumed"] == 1


# ------------------------------------------------------- router fleet

def test_router_parked_conversation_survives_drained_replica(lm, adapter,
                                                             tmp_path):
    """The store is FLEET-GLOBAL: a conversation parked by a replica that
    is then drained out of the fleet resumes on a survivor, bit-identical
    — the parking replica does not need to outlive its parks."""
    submits = [dict(prompt=P[0], max_new_tokens=12),
               dict(prompt=P[1], max_new_tokens=12)]
    solo = Router(lm, 2, rng=jax.random.key(42), block_steps=K,
                  park_dir=str(tmp_path / "solo"))
    solo.register_adapter("a0", adapter, ACFG)
    for s in submits:
        solo.submit(**s)
    solo.run()
    oracle = {c.request_id: c.tokens.tolist() for c in solo.completed}

    r = Router(lm, 2, rng=jax.random.key(42), block_steps=K,
               park_dir=str(tmp_path / "park"))
    r.register_adapter("a0", adapter, ACFG)
    rids = [r.submit(**s) for s in submits]
    r.step_block()
    r.step_block()
    # park whichever stream replica 1 holds, then drain replica 1 away
    parked = next(rid for rid in rids if r._records[rid].replica == 1)
    r.engines[1].park(parked)
    assert parked in r.parked_ids()
    r.drain(1)
    while r.step_block():
        pass                                  # drain completes, fleet of 1
    out = r.resume_parked(parked)             # lands on the survivor
    assert out == parked
    r.run()
    got = {c.request_id: c.tokens.tolist() for c in r.completed}
    assert got == oracle
