"""Host-only scheduler-simulation model: a stub :class:`CausalLM` whose
insert/extend/decode "programs" are zero-cost host no-ops with the SAME
slot and page accounting as the real thing (ROADMAP #18).

Million-request soak runs exist to measure the SCHEDULER — EDF admission,
WFQ placement, shed/expiry, page planning, harvest — not XLA. With a real
model every block pays a device dispatch (~ms), so a 1M-request run would
spend hours measuring the accelerator instead of the host hot paths. A
:class:`SimCausalLM` removes the device entirely:

* ``insert``/``extend`` run the full paged admission lifecycle
  (``PagedKVCache.plan``/``commit``, prefix-index registration, the same
  :class:`PagePoolExhausted` behaviour, atomic rollback) — page accounting
  is bit-identical to the real engine's — but write no KV bytes;
* decode blocks come from :meth:`sim_decode_block`: a deterministic pure
  function of (request id, token index) producing the emitted (K, slots)
  token matrix in numpy — never a jax call, never an XLA execution;
* ``ServeEngine`` detects ``lm.sim`` and routes its sampling sites here,
  so a soak run performs ZERO XLA executions after construction.

The scheduler sees exactly the state machine it would see in production
(slot claims, page pressure, retire cadence, deadline expiry), which is
what makes ``scripts/soak.py``'s ``router_sched_overhead_us_per_request``
an honest scheduler number: with no device time to hide behind, the whole
wall clock IS the host side. ``tests/test_sched_perf.py`` pins that a sim
engine's admission schedule (start/first-token/retire blocks per request)
equals a real tiny-model engine's on the same trace.

Unsupported in sim mode (each raises early): LoRA adapters, grammars,
host-tier spill, disaggregation handoffs, snapshots — none participate in
the soak's hot paths.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from neuronx_distributed_tpu.inference.paged_cache import PagedKVCache


@dataclasses.dataclass
class SimConfig:
    vocab_size: int = 32000
    max_seq_len: int = 64
    page_size: int = 0
    page_pool_pages: int = 0


@dataclasses.dataclass
class SimSession:
    """Host mirror of a decode session: no device cache (``cache=None`` —
    the engine's table-install seams are guarded on that), real
    :class:`PagedKVCache` accounting in paged mode."""

    lengths: np.ndarray
    active: np.ndarray
    cache: Optional[object] = None
    paged: Optional[PagedKVCache] = None
    adapters: Optional[object] = None
    grammars: Optional[object] = None


class SimCausalLM:
    """Drop-in stub for the :class:`CausalLM` surface ``ServeEngine``
    drives, with every device program replaced by host accounting."""

    sim = True
    lora = False
    grammar = False
    prefix_cache = True

    def __init__(self, max_batch: int = 4, buckets: Sequence[int] = (8, 16),
                 max_seq_len: int = 64, vocab_size: int = 32000,
                 page_size: int = 0, page_pool_pages: int = 0,
                 prefix_cache: bool = True, kv_token_bytes: int = 1024):
        self.max_batch = int(max_batch)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.paged = page_size > 0
        self.prefix_cache = bool(prefix_cache)
        self.config = SimConfig(vocab_size=int(vocab_size),
                                max_seq_len=int(max_seq_len),
                                page_size=int(page_size),
                                page_pool_pages=int(page_pool_pages))
        self._kv_token_bytes = int(kv_token_bytes)
        self.compile_ms = {}
        self.tracer = None
        self._decode = self.sim_decode_block   # sentinel: already "compiled"
        self._vocab_mod = max(self.config.vocab_size - 1, 1)

    # --- compile / session surface ---------------------------------------

    def compile(self) -> "SimCausalLM":
        return self

    def start_session(self) -> SimSession:
        session = SimSession(
            lengths=np.zeros((self.max_batch,), np.int64),
            active=np.zeros((self.max_batch,), bool))
        if self.paged:
            session.paged = PagedKVCache(
                self.config.page_size, self.config.page_pool_pages,
                self.max_batch, self.config.max_seq_len,
                prefix_cache=self.prefix_cache)
        return session

    def _bucket_for(self, s: int) -> int:
        for b in self.buckets:
            if s <= b:
                return b
        raise ValueError(
            f"prompt length {s} exceeds largest bucket {self.buckets[-1]}")

    def kv_cache_bytes(self) -> dict:
        tokens = (self.config.page_pool_pages * self.config.page_size
                  if self.paged else self.max_batch * self.config.max_seq_len)
        slab = self.max_batch * self.config.max_seq_len
        # host-only sim: no mesh, so per-chip == global (the real lm's
        # kv_bytes_global key — replay.run_trace's paged report reads it)
        return {"kv_bytes": tokens * self._kv_token_bytes,
                "kv_bytes_global": tokens * self._kv_token_bytes,
                "kv_slab_bytes": slab * self._kv_token_bytes}

    # --- the deterministic token function ---------------------------------

    def sim_token(self, rid: int, t: int) -> int:
        """Token t of request rid: a fixed mixing function into
        [1, vocab) — deterministic, id-keyed, never the pad token. The
        sim oracle's analogue of the per-request rng contract: the stream
        is a pure function of (request id, token index), independent of
        placement, batching, and block size."""
        return 1 + (rid * 1000003 + t * 7919) % self._vocab_mod

    def sim_first_tokens(self, rids: Sequence[int],
                         counts: Sequence[int]) -> List[int]:
        return [self.sim_token(int(r), int(c))
                for r, c in zip(rids, counts)]

    def sim_decode_block(self, steps: int, tok, active, done, counts,
                         rids) -> np.ndarray:
        """One K-step decode block for the whole pool, pure numpy: the
        emitted (K, max_batch) token matrix (pad for inactive/frozen
        slots — the engine's host mirror latches done exactly as it does
        for the fused device scan).

        ASYNC LOOP (``ServeEngine(async_loop=True)``): the sim "dispatch"
        stays eager — the matrix is host-known immediately — but the
        engine still queues it as an in-flight record and defers every
        RECORD to the pipelined harvest one iteration later, feeding this
        function the ``done`` input the real device would have carried out
        of the previous block (``ServeEngine._sim_end_done``). That is
        what keeps a sim soak's admission/retire schedule bit-identical
        to a real async engine's, so the sim-vs-real schedule pins of
        ``tests/test_sched_perf.py`` extend to the pipelined loop."""
        out = np.zeros((int(steps), self.max_batch), np.int64)
        idx = np.arange(int(steps), dtype=np.int64)
        for s in range(self.max_batch):
            if active[s] and not done[s]:
                out[:, s] = 1 + ((int(rids[s]) * 1000003
                                  + (int(counts[s]) + idx) * 7919)
                                 % self._vocab_mod)
        return out

    # --- insert / extend / retire (host accounting only) ------------------

    def insert(self, session: SimSession, slot_ids, prompt_ids,
               lengths=None, pad_token_id: int = 0, reserve_tokens=None,
               adapter_slots=None, ns=None, first=None):
        """Paged admission with the REAL plan/commit lifecycle (page holds,
        prefix registration, atomic rollback on pool pressure) and zero
        device work; the contiguous branch is pure length bookkeeping.
        Returns None — the engine's sim branch samples via
        :meth:`sim_token` instead of reading logits."""
        slot_ids = np.asarray(slot_ids, np.int32).reshape(-1)
        rows = len(slot_ids)
        if lengths is None:
            lengths = np.asarray(
                [int(np.max(np.nonzero(prompt_ids[i])[0], initial=0)) + 1
                 for i in range(rows)], np.int32)
        lengths = np.maximum(np.asarray(lengths, np.int32), 1)
        if session.paged is not None:
            pkv = session.paged
            if reserve_tokens is None:
                totals = np.full((rows,), self.config.max_seq_len, np.int64)
            else:
                totals = lengths.astype(np.int64) + np.broadcast_to(
                    np.asarray(reserve_tokens, np.int64), (rows,))
            nss = list(ns) if ns is not None else [None] * rows
            plans = []
            try:
                for i in range(rows):
                    plans.append(pkv.plan(
                        prompt_ids[i, : lengths[i]].tolist(),
                        int(totals[i]), ns=nss[i]))
            except Exception:
                for p in plans:
                    pkv.rollback(p)
                raise
            for i in range(rows):
                pkv.commit(int(slot_ids[i]), plans[i],
                           prompt_ids[i, : lengths[i]].tolist(), ns=nss[i])
        session.lengths[slot_ids] = lengths
        session.active[slot_ids] = True
        return None

    def extend(self, session: SimSession, slot_ids, ids, new_len, starts,
               tables=None, adapter_slots=None, first=None):
        """Chunk-extend accounting: the chunk's page allocation already
        happened in ``PagedKVCache.extend_chunked`` (the engine drives it
        exactly like the real path); nothing device-side to do."""
        return None

    def retire(self, session: SimSession, slot_ids) -> None:
        slot_ids = np.asarray(slot_ids, np.int32).reshape(-1)
        if len(slot_ids) == 0:
            return
        session.active[slot_ids] = False
        if session.paged is not None:
            for slot in slot_ids:
                session.paged.release(int(slot))
