"""Continuous-batching serving engine: a host-side request scheduler driving
the fused multi-slot session programs of :class:`CausalLM`.

Role-parity with the reference's serving loop (``model_wrapper.py``'s
``seq_ids`` continuous batching + the generation loop of
``examples/inference/runner.py``), restructured around the dispatch-floor
finding of PROFILE.md r5/r6: the host→device program dispatch (3.8–6.7 ms on
this harness) dominates per-token serving cost, so the engine advances the
WHOLE slot pool K tokens per dispatch (``CausalLM.compile_session_decode_
fused``) and touches the host exactly twice per block — one program call,
one fetch of the emitted (K, slots) token matrix. Everything the scheduler
needs between blocks (per-slot lengths, EOS/overflow latches) is a pure
function of that fetch and the block inputs, so the host mirrors the
on-device state without extra reads.

Scheduler responsibilities (all host-side, between blocks):

* admission queue — requests wait until a slot frees AND their arrival time
  (virtual, in blocks) has passed;
* bucketed prefill batching — queued requests sharing a prefill bucket are
  admitted together through ONE right-sized ``insert`` (prefill width =
  number of admitted prompts, scatter cost O(admitted rows));
* CHUNKED prefill (``prefill_chunk_tokens > 0``) — a prompt longer than the
  chunk budget is admitted into a slot but prefilled across scheduling
  rounds, at most ``prefill_chunk_tokens`` prompt tokens per round
  (``CausalLM.extend``), INTERLEAVED with the decode blocks of every active
  slot: Sarathi-Serve's stall-free batching on top of the Orca-style
  iteration-level scheduling above. A one-shot insert of a long prompt
  stalls every live token stream for the whole prefill; chunking bounds the
  per-round prefill work, so inter-token latency during an insert stays
  near the no-insert baseline (the replay report's
  ``decode_stall_excess_ms`` measures exactly this). No token is emitted
  until the final chunk; in paged mode pages are allocated chunk-by-chunk
  (``PagedKVCache.begin/extend/finish_chunked``) and pool pressure
  mid-prefill rolls the whole admission back atomically;
* retire-on-EOS / budget / cache-room — finished slots are retired at block
  boundaries and immediately reusable; ``cancel`` retires a request in ANY
  state (queued / mid-prefill / decoding);
* per-request samplers — greedy flag + temperature ride per-slot device
  arrays into the compiled program (:class:`SlotSampler`); ``top_k``/
  ``top_p`` are engine-wide statics validated at submit;
* per-request rng — request r's t-th token draws from
  ``fold_in(fold_in(base, r), t)``, so a sampled stream is a pure function
  of (prompt, params, base key, request id): bit-identical across fused vs
  stepwise, paged vs contiguous, AND chunked vs one-shot admission, no
  matter how the schedules interleave.

Exactness invariant: with ``fused=False`` the engine replays the identical
schedule through per-token ``step()`` dispatches (same admission cadence,
same per-request keys, same sampler math), and both modes emit token
streams bit-identical to each other and — for greedy requests — to a solo
``CausalLM.generate`` of the same prompt.

Fault tolerance (the overload / fault / crash layer on top):

* per-request DEADLINES — ``submit(..., ttft_deadline_ms=, deadline_ms=)``
  converts wall budgets to the virtual block clock (``block_time_ms`` per
  block); admission is earliest-deadline-first among arrived requests, a
  queued or mid-prefill request whose deadline passed is expired (chunked
  pages rolled back atomically through the cancel machinery) and a decoding
  request past its completion deadline retires NOW with a partial,
  ``expired=True`` completion;
* BOUNDED admission queue — ``max_queue``/``shed_policy`` cap the arrived
  backlog: the overflow victim gets a structured :class:`Rejected`
  (retry-after estimate included) instead of queueing unboundedly, so
  goodput under overload stays at capacity instead of collapsing into
  universally-missed deadlines (Clipper's discipline);
* deterministic FAULT INJECTION (``faults=FaultPlan(...)``, see
  ``inference/faults.py``) — seeded ``PagePoolExhausted`` storms at the
  allocator, transient insert/extend/decode dispatch failures absorbed by
  retry+exponential backoff (escalating to :class:`DispatchFailed` past the
  budget), and corrupted-page reads recovered by physically re-prefilling
  the affected requests (streams stay bit-identical — the per-request rng
  contract);
* HOST-MEMORY KV TIER (``host_tier_pages > 0``, paged mode) — pool
  exhaustion becomes a spill/restore cycle instead of a shed event: cold
  cache-only prefix pages spill into checksummed host buffers (radix
  entries retained, marked tiered), a prefix hit on a tiered path restores
  the pages into fresh device pages before admission, and the admission
  ladder is spill → restore-budget → re-prefill → shed, making
  ``PagePoolExhausted`` a last resort. The tier is inclusive, so a
  corrupted DEVICE page with a live tier copy repairs in place instead of
  replaying. A failed/corrupt restore (the ``tier`` fault seam) only ever
  degrades to re-prefill — never a wrong token;
* SNAPSHOT/RESTORE — ``snapshot()`` at any block boundary serializes the
  scheduler + per-request state (prompt, generated tokens, rng base,
  deadlines, chunk progress) to a JSON-able dict;
  :meth:`ServeEngine.from_snapshot` re-admits every in-flight request by
  replaying prompt+generated through the prefill path (radix prefix pages
  are reused where they survive) and resumes each stream BIT-IDENTICAL from
  the interruption point — token t of request r always draws from
  ``fold_in(fold_in(base, r), t)``, so recovery is provable, not hopeful.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import deque
from collections.abc import MutableMapping
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.observability import (
    FlightRecorder,
    MetricsRegistry,
    SLOMonitor,
    Tracer,
)
from neuronx_distributed_tpu.observability import attribution as _attribution
from neuronx_distributed_tpu.inference.adapters import (
    AdapterLoadError,
    AdapterPoolExhausted,
)
from neuronx_distributed_tpu.inference.causal_lm import (
    CausalLM,
    FirstToken,
    _set_block_tables,
    _set_cache_index_rows,
)
from neuronx_distributed_tpu.inference.grammar import (
    GrammarLoadError,
    GrammarPoolExhausted,
)
from neuronx_distributed_tpu.inference.faults import (
    DispatchFailed,
    FaultInjector,
    FaultPlan,
    TransientDispatchError,
)
from neuronx_distributed_tpu.inference.paged_cache import (
    ChunkedPrefill,
    PagePoolExhausted,
)
from neuronx_distributed_tpu.inference.sampling import Sampler, SlotSampler
from neuronx_distributed_tpu.models.llama import KV_PAGE_LEAVES, KV_SCALE_LEAVES, leaf_paths
from neuronx_distributed_tpu.utils.compile_cache import PARTS as _COMPILE_PARTS, compile_log
from neuronx_distributed_tpu.inference.schedq import (
    AdmissionQueue,
    admission_deadline,
    shed_deadline_key,
)


@dataclasses.dataclass
class Request:
    """One admission-queue entry. ``arrival_block`` is virtual time in decode
    blocks (deterministic across backends — wall-clock traces would make CPU
    equivalence tests racy); the engine admits the request at the first block
    boundary >= arrival with a free slot."""

    request_id: int
    prompt: np.ndarray              # (s,) int32
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    temperature: float = 0.0        # 0.0 => greedy
    greedy: bool = True
    arrival_block: int = 0
    submit_block: int = 0           # block counter when submitted
    start_block: Optional[int] = None
    first_token_block: Optional[int] = None
    # absolute virtual-time deadlines (None = none): first token must land
    # by ttft_deadline_block, the whole stream by deadline_block
    ttft_deadline_block: Optional[int] = None
    deadline_block: Optional[int] = None
    # multi-tenant isolation label (the Router's fairness/quota unit; a
    # bare engine just carries it through to the completion)
    tenant: str = "default"
    # multi-LoRA serving: name of the registered adapter this request's
    # tokens must be sampled under (None = the base model / identity slot).
    # Admission loads+pins it in the session's AdapterPool; retire unpins.
    adapter: Optional[str] = None
    # structured decoding: name of the registered grammar this request's
    # stream must match (None = free-form / identity slot 0). Admission
    # loads+pins its token-DFA tables in the session's GrammarPool; the
    # fused scan enforces the mask per step; retire unpins.
    grammar: Optional[str] = None


@dataclasses.dataclass
class Completion:
    request_id: int
    tokens: np.ndarray              # generated ids (eos included when hit)
    prompt_len: int
    queue_blocks: int               # admission wait (blocks, virtual time)
    decode_blocks: int              # blocks from insert to retirement
    ttft_blocks: int = 0            # arrival -> first token (virtual blocks)
    # wall perf_counter stamp per emitted token (the block fetch that
    # surfaced it) — the replay/recovery bookkeeping's record of what was
    # already delivered; the inter-token-latency REPORT reads the tracer's
    # token events instead (replay.run_trace — single source of truth with
    # the Perfetto export)
    token_ts: Optional[np.ndarray] = None
    cancelled: bool = False
    # deadline surface: ``expired`` = the ENGINE cut the request off when
    # its deadline passed (tokens hold whatever was delivered by then);
    # ``deadline_missed`` also covers requests that finished late
    expired: bool = False
    deadline_missed: bool = False
    tenant: str = "default"
    adapter: Optional[str] = None
    grammar: Optional[str] = None
    # why the stream ended (ISSUE 13 satellite — callers previously had to
    # DIFF fields to infer this): "eos" (sampled its eos id), "budget"
    # (max_new_tokens exhausted), "expired" (deadline cut it off),
    # "grammar_accept" (the token DFA entered an accept-terminal state —
    # the structured-decoding EOS), or "cancelled"
    finish_reason: str = "budget"


@dataclasses.dataclass
class Rejected:
    """Load-shed verdict: the bounded admission queue refused this request
    (``shed_policy`` picked it as the overflow victim). ``retry_after_blocks``
    is the backlog-drain estimate — resubmitting after that many blocks has
    a fresh admission chance; resubmission gets a NEW request id (and, by
    the per-request rng contract, a fresh but deterministic stream)."""

    request_id: int
    retry_after_blocks: int
    queue_depth: int
    reason: str = "queue_full"


@dataclasses.dataclass
class ReplicaLoad:
    """One typed load summary per engine/replica (ISSUE 12 satellite):
    the SAME struct feeds router placement (``Router._load_score``), the
    autoscaling policy's signals, the router's ``replica_states()`` cards
    and the incident bundle's ``state_summary()`` — one shape instead of
    three ad-hoc dict readings of the same scheduler state. Every field is
    a deterministic block-clock quantity except ``slo_alerting``, which is
    only as deterministic as the objectives the monitor watches (see
    observability/slo.py)."""

    role: str
    queue_depth: int                 # queued, not yet admitted
    prefilling: int                  # mid-chunked-prefill slots
    replays: int                     # pending recovery replays
    backlog: int                     # queue + prefilling + replays
    active_slots: int
    free_slots: int
    # 0 when a free slot + pool room could take typical work NOW, else the
    # soonest-retirement estimate plus the backlog (blocks); placement
    # refines the zero case per-request via _pool_can_admit
    est_ttft_blocks: int
    pool_retry_after_blocks: int
    pages_in_use: Optional[int] = None     # None without a paged pool
    pages_free: Optional[int] = None
    tier_pages: Optional[int] = None       # None without a host tier
    adapters_resident: Optional[List[str]] = None   # None without LoRA
    slo_alerting: bool = False       # any burn rule latched right now
    decode_blocks: int = 0
    inserted_requests: int = 0
    # undelivered token budgets (ROADMAP #18): the router's fleet-wide
    # retry-after estimate reads these off the per-block cached summary
    # instead of re-scanning every replica's slots and queue per shed
    inflight_tokens: int = 0         # sum over live slots of remaining budget
    queued_tokens: int = 0           # sum over queued requests' budgets
    # the block whose EMISSIONS this summary reflects (PR 19 remainder):
    # under async_loop the harvest trails the dispatch clock by the
    # in-flight block, so a router reading the summary at block B sees
    # counters as of B-1 — the autoscaler compensates its patience with
    # (router.blocks - observed_block) instead of scaling a block late
    observed_block: int = 0
    # conversations this replica holds ONLY as park records (0 device +
    # 0 host pages): capacity planning reads resident vs parked load
    parked: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _PrefillInFlight:
    """Host state of one chunked admission: the slot is claimed (not free)
    but decode-inactive until the final chunk lands and its first token is
    sampled. ``chunk`` carries the paged page bookkeeping (None on the
    contiguous slab)."""

    req: Request
    slot: int
    written: int                    # prompt tokens in KV (incl. reused prefix)
    chunk: Optional[ChunkedPrefill] = None


# the engine's pre-observability counter set: every key the legacy
# ``engine.stats`` dict carried, now backed by MetricsRegistry counters
# (exposition name ``serve_<key>``) through the dict-compatible view below —
# the parity test in tests/test_observability.py pins this list
# every cache leaf whose axis 1 is the physical PAGE axis — what the
# page-IO closures (tier spill/restore, handoff framing, corruption
# seam) move per page. int8 pools add the per-(page, head) fp32 scale
# leaves; a page's bytes and its scales always travel (and garble, and
# CRC) together.
_KV_PAGE_LEAVES = leaf_paths(KV_PAGE_LEAVES + KV_SCALE_LEAVES)


def _page_payload(data: Dict[str, np.ndarray], path: str):
    """What a page payload holds for the cache leaf at ``path`` (None:
    nothing). Payloads are keyed by the leaf's whole tree path, and a parked
    record or a handoff may come from a build whose K/V leaves sat under
    another prefix (beside ``cache_index`` until PR 27): there the leaf's own
    name, the path's last component, finds it."""
    if path in data:
        return data[path]
    name = path[path.rindex("['"):]
    named = [k for k in data if k.endswith(name)]
    return data[named[0]] if len(named) == 1 else None


def _startup_stats() -> Dict[str, int]:
    """What the PROCESS's start-up spent compiling, from its compile log
    (utils/compile_cache.py) at the moment an engine is built, as
    ``engine.stats`` keeps it (``_STAT_KEYS``: whole milliseconds and one
    count, each one ``setup.*`` metric of the benchmark). The first four are
    sums over the rows ``CausalLM._time_compile`` opened, the ones
    ``compile_ms`` has a key for."""
    programs, weights = compile_log.sums("programs"), compile_log.sums("weights")
    values = {
        "startup_trace_lower_ms": programs.get("trace_lower_ms", 0.0),
        "startup_xla_compile_ms": programs.get("xla_compile_ms", 0.0),
        "startup_cache_load_ms": programs.get("cache_load_ms", 0.0),
        "startup_format_ms": programs.get("asking_ms", 0.0) + programs.get("relay_ms", 0.0),
        "startup_cache_misses": compile_log.counts()["cache_misses"],
        "startup_unnamed_ms": compile_log.unnamed["ms"],
        "startup_init_ms": sum(weights.get(part, 0.0) for part in (
            "abstract_ms", "trace_lower_ms", "xla_compile_ms", "cache_load_ms")),
    }
    return {key: int(round(value)) for key, value in values.items()}


_STAT_KEYS = (
    "blocks", "decode_blocks", "inserts", "inserted_requests",
    "program_calls", "host_fetches",
    # host arrays that went up WITH the fused blocks' calls (the rows'
    # mirrors: _advance_block), summed over the blocks
    "block_uploads", "deferred_admissions",
    "chunk_program_calls", "prefill_chunk_tokens_done", "prefill_aborts",
    "cancelled", "rejected", "shed_evictions", "expired",
    "dispatch_retries", "corrupt_page_replays", "restored_requests",
    "tier_page_repairs",
    "adapter_rejects", "adapter_load_retries",
    "grammar_rejects", "grammar_load_retries",
    "handoffs_sent", "handoffs_adopted",
    # conversation tier (ROADMAP #21): parks taken, exact resumes, resumes
    # degraded to the replay path, and resumes refused outright
    "parked", "resumed", "park_replays", "park_rejects",
    # streaming-report aggregates (ROADMAP #18): the memory-bounded trace
    # drivers (keep_completions=False) read the whole completion surface
    # from these counters + the latency histograms instead of materialized
    # per-request Completion lists
    "completed", "generated_tokens", "ontime_tokens", "deadline_misses",
    "queue_blocks_sum", "ttft_blocks_sum",
    # what the router chose for the live rows of the fused decode blocks
    # (models with experts; CausalLM.compile_session_decode_fused): expert
    # slots touched, assignments, layer steps, each summed over steps x layers
    "moe_experts_touched", "moe_assignments", "moe_layer_steps",
    # every pick of those rows' routers, picks of experts held elsewhere
    # included (moe/layer.py's share of a wider expert layer); equal to
    # moe_assignments where every expert is held
    "moe_assignments_routed",
    # those of them that fell on experts that cost nothing (moe/layer.py's
    # ``zero_experts``: no weights, no product; moe_assignments never counts
    # one), over the decode blocks and over the paged inserts, and beside the
    # second every pick of the inserts' real tokens
    "moe_zero_picks", "moe_insert_zero_picks", "moe_insert_assignments_routed",
    # the same three over the real tokens of the paged inserts (bucket
    # padding chooses nothing; CausalLM._paged_insert_programs), and the
    # grouped rows the inserts' experts gathered, multiplied and combined,
    # real or not (every pick where every expert is held; passes x the row
    # bound where a share is, moe/expert_mlps.py::row_bound), and the rows
    # the grouped kernel's dots ran over (the sub-tiles its groups touched:
    # kernels/grouped_matmul.py::rows_multiplied): assignments / rows is the
    # share of the rows handled that was real work, assignments /
    # rows_multiplied the share of the MXU's, touched / layer_calls the
    # experts an insert's layer read. moe_insert_passes: the passes the
    # layer calls made over their sorted lists (one a call where every expert
    # is held; ceil(real picks / bound) a slice where a share is), so
    # passes / layer_calls reads 1.0 where the bound always held
    "moe_insert_experts_touched", "moe_insert_assignments",
    "moe_insert_layer_calls", "moe_insert_rows", "moe_insert_rows_multiplied",
    "moe_insert_passes",
    # the insert's twins of program_calls / host_fetches: compiled-program
    # calls that admitted requests (an insert; each chunk extend of a chunked
    # or replayed admission) and fetches of their first tokens. An insert is
    # one of each, so (calls + fetches) / inserts reads 2.0 where every
    # admission was a one-shot insert and more where one went by chunks
    "insert_program_calls", "insert_host_fetches",
    # of insert_program_calls, those whose rows all started at position 0 (no
    # prefix hit, no earlier chunk): fresh / calls is how often latent
    # attention's prompt form reads the call's own tokens and no slab
    # (models/deepseek_v2.py); 1.0 on unshared one-shot prompts
    "insert_programs_fresh",
    # inserts whose first tokens were left on the device at dispatch because
    # nothing on it waited for them (_insert_group), inserts dispatched while
    # an earlier one was still unfetched (the host planned and dispatched
    # them while the device ran that one: overlapped / inserts is the share),
    # and one-token rows that gave their slot and pages back at dispatch
    "insert_fetches_deferred", "inserts_overlapped",
    "slots_released_at_dispatch",
    # how far the fused decode blocks read the cache, and of how many rows:
    # slots read of a row (models/llama.py::KVWalk, chunk rounding included)
    # summed over the steps that had a live row, those steps, and the slots
    # read summed over the rows of each step's rung; tokens / (steps x
    # max_seq_len) is the share of the logical slab a step read, row_slots /
    # (tokens x max_batch) the share of that rectangle's rows
    "kv_walk_tokens", "kv_walk_steps", "kv_walk_row_slots",
    # a model with per-slot state beside its pages (lm.slot_rows;
    # models/granite_hybrid.py): the real tokens its recurrence scanned in
    # the paged inserts and the positions it ran over (rows x the bucket
    # rounded up to the scan's chunk)
    "ssm_scan_tokens", "ssm_scan_positions",
    # a model whose window layers keep a ring a slot (models/laguna.py): the
    # ring slots those layers' decode steps read of the rows of their rung
    # (rung rounding included) and the tokens inside the window they needed
    # (min(reach, window) a live row a window layer-step). kv_walk_* above
    # count the layers that page, the full layers, alone
    "kv_window_slots_read", "kv_window_slots_needed",
    # a model that scores its cached tokens and reads a chosen set
    # (models/deepseek_v32.py), a live row a sparse layer-step each: the
    # tokens visible to it, the tokens chosen for it (min(reach, index_topk))
    # and the latent slots the step read for it and the other rows of its
    # rung; selected / visible is the share the choice kept, read / selected
    # 1.0 where a step reads the chosen alone
    "dsa_tokens_visible", "dsa_tokens_selected", "dsa_latent_slots_read",
    # a model that carries several residual streams (models/xing4.py), times
    # the stream mixes a token passes (config.stream_mixes): the real tokens
    # its insert programs mixed, the token slots those programs ran over
    # (rows x bucket, padding included; both host numbers, no fetch), and the
    # live rows of its decode steps (with the block's walk sums); tokens /
    # slots is the share of the n-wide stream traffic spent on real tokens
    "mhc_mix_tokens", "mhc_mix_slots", "mhc_mix_steps",
    # weight leaves the lm re-laid ONCE into the layout its one-token step
    # reads them in, and their bytes (CausalLM._hold; 0 where the backend's
    # compiler keeps the default, as the CPU's does): set when the engine is
    # built, by which time ``lm.compile()`` has settled them
    "param_relaid_leaves", "param_relaid_bytes",
    # what the PROCESS's start-up spent compiling (``_startup_stats``), set
    # when the engine is built
    "startup_trace_lower_ms",      # Python tracing and lowering: paid cached or not
    "startup_xla_compile_ms",      # the backend's compile of what missed the cache
    "startup_cache_load_ms",       # executables read back from the persistent cache
    "startup_format_ms",           # the format pass: an asking compile thrown away, the re-lay
    "startup_cache_misses",        # of the whole process, named programs or not
    "startup_unnamed_ms",          # compilations no program owns: eager ops
    "startup_init_ms",             # the weights' program, its run (the draw) apart
)


class _StatsView(MutableMapping):
    """Dict-compatible view over :class:`MetricsRegistry` counters: the
    legacy ``engine.stats["blocks"] += 1`` surface keeps working verbatim
    while the SAME store feeds the Prometheus exposition (one counter, two
    read paths — no drift possible). New keys register on first write, so
    ad-hoc ``setdefault`` counters keep working too."""

    def __init__(self, registry: MetricsRegistry, keys=(),
                 prefix: str = "serve_"):
        self._reg = registry
        self._prefix = prefix
        self._counters = {k: registry.counter(prefix + k) for k in keys}

    def __getitem__(self, k):
        c = self._counters.get(k)
        if c is None:
            raise KeyError(k)
        return c.value

    def __setitem__(self, k, v) -> None:
        c = self._counters.get(k)
        if c is None:
            c = self._reg.counter(self._prefix + k)
            self._counters[k] = c
        c.set(v)

    def __delitem__(self, k) -> None:
        raise TypeError("stats counters cannot be deleted")

    def __iter__(self):
        return iter(self._counters)

    def __len__(self) -> int:
        return len(self._counters)

    def __repr__(self) -> str:
        return repr(dict(self))


class ServeEngine:
    """Continuous-batching scheduler over one :class:`CausalLM` session.

    ``block_steps`` is the fused-K knob: each scheduling round advances every
    live slot K tokens (one dispatch + one fetch with ``fused=True``; K
    per-token dispatches with ``fused=False`` — the measurement baseline).
    Larger K amortizes dispatch further but (a) delays admission/retirement
    by up to K-1 tokens (queued work waits longer, finished slots hold their
    cache rows longer) and (b) over-generates up to K-1 discarded tokens per
    finished request. K ~ 8-16 is the sweet spot on the measured 3.8-6.7 ms
    dispatch floor.

    ``prefill_chunk_tokens`` is the stall-free-batching knob: 0 keeps
    one-shot admission (a long prompt's whole prefill runs between two
    decode blocks — every live stream stalls for it); C > 0 prefills any
    prompt longer than C across rounds, at most C prompt tokens per round,
    between the pool's decode blocks. Smaller C tightens the inter-token
    latency bound on live streams but stretches the new request's TTFT (its
    prompt needs ceil(len/C) rounds, each also paying a K-token decode
    block) — the TTFT-vs-ITL tradeoff the README documents. Chunking also
    lifts the bucket ceiling: a prompt longer than the largest prefill
    bucket is serveable chunked (each chunk rides its own bucket), as long
    as it still fits the cache room. Token streams are bit-identical to
    one-shot admission in every mode (the per-request rng contract).
    """

    def __init__(
        self,
        lm: CausalLM,
        block_steps: int = 8,
        fused: bool = True,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        pad_token_id: int = 0,
        rng: Optional[jax.Array] = None,
        prefill_chunk_tokens: int = 0,
        max_queue: Optional[int] = None,
        shed_policy: str = "tail",
        block_time_ms: float = 1.0,
        faults: Optional[Union[FaultPlan, FaultInjector]] = None,
        dispatch_retries: int = 3,
        dispatch_backoff_s: float = 0.001,
        host_tier_pages: int = 0,
        trace: bool = False,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        name: Optional[str] = None,
        slos: Optional[Sequence] = None,
        incident_dir: Optional[str] = None,
        incident: Optional[FlightRecorder] = None,
        incident_window_blocks: int = 16,
        incident_burst_threshold: int = 3,
        incident_burst_window: int = 8,
        role: str = "both",
        keep_completions: bool = True,
        async_loop: bool = False,
        park_idle_blocks: int = 0,
        park_dir: Optional[str] = None,
        park_store=None,
    ):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got {role!r}")
        if role != "both" and not getattr(lm, "paged", False):
            raise ValueError(
                "disaggregated roles require a paged CausalLM — the "
                "prefill→decode handoff moves KV as physical pages "
                "(inference/disagg.py)")
        if block_steps < 1:
            raise ValueError(f"block_steps must be >= 1, got {block_steps}")
        if prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0, got {prefill_chunk_tokens}")
        if prefill_chunk_tokens > lm.buckets[-1]:
            raise ValueError(
                f"prefill_chunk_tokens {prefill_chunk_tokens} exceeds the "
                f"largest prefill bucket {lm.buckets[-1]} (each chunk must "
                f"ride a compiled bucket)")
        if shed_policy not in ("tail", "deadline"):
            raise ValueError(
                f"shed_policy must be 'tail' or 'deadline', got {shed_policy!r}")
        if max_queue is not None and max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue}")
        if block_time_ms <= 0:
            raise ValueError(f"block_time_ms must be > 0, got {block_time_ms}")
        if dispatch_retries < 0:
            raise ValueError(f"dispatch_retries must be >= 0, got {dispatch_retries}")
        if host_tier_pages < 0:
            raise ValueError(
                f"host_tier_pages must be >= 0, got {host_tier_pages}")
        if host_tier_pages and not getattr(lm, "paged", False):
            raise ValueError("host_tier_pages requires a paged CausalLM")
        if host_tier_pages and not getattr(lm, "prefix_cache", True):
            raise ValueError(
                "host_tier_pages requires prefix_cache=True (the tier "
                "retains radix entries — without the index there is "
                "nothing to mark tiered)")
        # host-only scheduler simulation (inference/simlm.py): a stub lm
        # whose insert/decode programs are zero-cost host no-ops with the
        # same slot/page accounting — million-request soaks never execute
        # XLA. The engine routes its sampling sites through the stub's
        # deterministic token function instead of jax.
        self._sim = bool(getattr(lm, "sim", False))
        if self._sim and host_tier_pages:
            raise ValueError("sim engines have no device pages to tier")
        # persistent conversation tier (ROADMAP #21): parking exports KV
        # PAGES, so the paged pool is the park unit — contiguous-slab and
        # sim engines have nothing exportable below the host tier
        if park_idle_blocks < 0:
            raise ValueError(
                f"park_idle_blocks must be >= 0, got {park_idle_blocks}")
        if park_idle_blocks or park_dir is not None or park_store is not None:
            if park_dir is not None and park_store is not None:
                raise ValueError("pass park_dir OR park_store, not both")
            if park_dir is None and park_store is None:
                raise ValueError(
                    "park_idle_blocks requires park_dir or park_store — "
                    "the park has to land somewhere durable")
            if self._sim:
                raise ValueError("sim engines have no KV pages to park")
            if not getattr(lm, "paged", False):
                raise ValueError(
                    "conversation parking requires a paged CausalLM "
                    "(KV pages are the park unit)")
        # a model with per-slot state beside its pages (lm.slot_rows): what
        # moves a slot's cache BY PAGES would leave its state behind
        if getattr(lm, "slot_rows", ()):
            refused = {
                "role='prefill' / 'decode' (the handoff moves pages)": role != "both",
                "host_tier_pages (the tier spills pages)": bool(host_tier_pages),
                "conversation parking (a park exports pages)": bool(
                    park_idle_blocks or park_dir is not None or park_store is not None),
            }
            for what, asked in refused.items():
                if asked:
                    raise ValueError(
                        f"this model keeps per-slot state {lm.slot_rows} beside its "
                        f"pages and is not served with {what}")
            if prefill_chunk_tokens and not getattr(lm, "slot_rows_continue", True):
                raise ValueError(
                    f"this model's {lm.slot_rows} are written by a whole prompt; "
                    "prefill_chunk_tokens would continue a row by chunks")
        self.lm = lm
        self.block_steps = int(block_steps)
        self.fused = bool(fused)
        # async double-buffered block loop (ROADMAP #22): dispatch block t,
        # run the whole scheduling pass, and only fetch block t-1's emissions
        # AFTER block t+1... i.e. the fetch always trails the dispatch by one
        # block, so the device never idles between blocks. JAX async dispatch
        # makes the split free: the fused program call returns device futures
        # immediately; np.asarray on the token matrix is the only sync. The
        # sync loop is retained verbatim (_step_block_sync) as the oracle —
        # streams are bit-identical by construction because every scheduling
        # decision commits on the virtual block clock, not on fetched data.
        if async_loop and not fused:
            raise ValueError(
                "async_loop requires fused=True — the double-buffered "
                "pipeline overlaps the fused K-step block program; the "
                "stepwise oracle is inherently synchronous")
        self.async_loop = bool(async_loop)
        # prefill/decode disaggregation role (inference/disagg.py): a
        # "prefill" worker runs ONLY insert/extend programs — a finished
        # prompt's first token is sampled here, its KV pages are packaged
        # into a checksummed KVHandoff (self.outbox) and the slot is
        # released; a "decode" worker runs only the fused decode scan plus
        # page adoption (adopt_handoff). "both" is the classic engine.
        self.role = role
        self.outbox: List = []
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.slot_sampler = SlotSampler(top_k=top_k, top_p=top_p)
        self.pad_token_id = int(pad_token_id)
        # overload / robustness knobs: deadlines are specified in ms and
        # converted to the virtual block clock at block_time_ms per block
        # (set it to the measured per-block wall time on real hardware; the
        # default 1.0 makes ms == blocks, the deterministic test basis);
        # max_queue bounds the ARRIVED backlog — overflow is shed per
        # shed_policy ('tail' drops the newest arrival, 'deadline' drops the
        # laxest deadline) with a structured Rejected verdict
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_policy = shed_policy
        self.block_time_ms = float(block_time_ms)
        self.dispatch_retries = int(dispatch_retries)
        self.dispatch_backoff_s = float(dispatch_backoff_s)
        # tracer lane process group: a bare engine records on ("engine", x);
        # a Router names each replica ("replica<i>") so one shared tracer
        # renders per-replica timelines side by side in Perfetto
        self.lane = str(name) if name else "engine"
        self._injector: Optional[FaultInjector] = None
        if faults is not None:
            self._injector = (faults if isinstance(faults, FaultInjector)
                              else FaultInjector(faults))
        # observability: the tracer records structured lifecycle/dispatch
        # events (disabled by default — one boolean check per seam); the
        # registry backs BOTH the Prometheus exposition and the legacy
        # ``stats`` dict view. Neither touches device programs: every event
        # derives from data the scheduler already holds between blocks.
        # A tracer the engine builds itself also mirrors its spans into the
        # device profiler's trace (``nxd:<name>`` on the host line, on the
        # profiler's clock); one handed in says for itself what it mirrors.
        self.tracer = tracer if tracer is not None else Tracer(
            enabled=bool(trace),
            annotate=jax.profiler.TraceAnnotation if trace else None)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # compile spans from lazily-compiled programs land on this tracer.
        # An ENABLED tracer always takes the lm; a disabled one only fills
        # a vacancy — a warm-up engine sharing the lm must not detach the
        # serving engine's tracer
        if self.tracer.enabled or getattr(lm, "tracer", None) is None:
            lm.tracer = self.tracer
        self._m_ttft = self.metrics.histogram(
            "serve_ttft_ms", help="wall submit->first-token latency")
        self._m_itl = self.metrics.histogram(
            "serve_itl_ms", help="wall gap between token deliveries")
        self._m_queue = self.metrics.gauge(
            "serve_queue_depth", help="arrived admission backlog")
        # ring-buffer drops surfaced as a counter: an exported trace or a
        # metrics scrape both learn the window is partial (ISSUE 9
        # satellite — drops were previously sidecar-only)
        self._m_dropped = self.metrics.counter(
            "trace_dropped_events",
            help="tracer ring-buffer events dropped (export is partial)")
        # decode-worker adoption cost (checksum verify + page alloc + device
        # writes) — the migration price tag next to serve_tier_restore_ms
        self._m_handoff = self.metrics.histogram(
            "serve_handoff_adopt_ms",
            help="migrated-prompt page adoption wall ms", lo=0.01)
        # conversation-tier price tags: park (page export + durable write +
        # eviction) and resume (durable read + verify + page adoption)
        self._m_park = self.metrics.histogram(
            "serve_park_ms",
            help="conversation park (export+store+evict) wall ms", lo=0.01)
        self._m_park_resume = self.metrics.histogram(
            "serve_park_resume_ms",
            help="parked-conversation resume (load+verify+adopt) wall ms",
            lo=0.01)
        # SLO burn-rate monitor (observability/slo.py): declarative
        # objectives evaluated once per block; None (the default) costs
        # nothing — the monitor is never constructed
        self._slo: Optional[SLOMonitor] = None
        if slos:
            self._slo = SLOMonitor(self.metrics, slos, tracer=self.tracer,
                                   lane=self.lane)
        # incident flight recorder (observability/incident.py): trigger
        # hooks at the failure seams dump bounded evidence bundles; a
        # Router shares ONE recorder across its replicas via ``incident=``
        self.incident: Optional[FlightRecorder] = incident
        if self.incident is None and incident_dir:
            self.incident = FlightRecorder(
                incident_dir, tracer=self.tracer, metrics=self.metrics,
                window_blocks=incident_window_blocks, source=self.lane)
        self._burst_threshold = int(incident_burst_threshold)
        self._burst_window = int(incident_burst_window)
        self._miss_blocks: deque = deque(maxlen=64)
        self._pool_pressure_blocks: deque = deque(maxlen=64)
        self._disp_hist: Dict[str, object] = {}
        self._submit_ts: Dict[int, float] = {}
        self._last_tok_ts: Dict[int, float] = {}
        # base key: request r's token t draws from fold_in(fold_in(rng, r), t)
        # (sim engines never sample — the stub's token function replaces
        # the whole rng surface, and the hot path stays jax-free)
        self.rng = (None if self._sim
                    else rng if rng is not None else jax.random.key(0))
        if lm._decode is None:
            lm.compile()
        self.session = lm.start_session()
        self.host_tier_pages = int(host_tier_pages)
        if self.host_tier_pages and self.session.paged is not None:
            # host-memory KV tier (ROADMAP #13): cold cache-only pages spill
            # into checksummed host buffers instead of dropping; the IO
            # closures read/write the session's page pools between blocks
            # (host-side only — no compiled program changes shape)
            self.session.paged.enable_tier(
                self.host_tier_pages,
                self._read_page_bytes, self._write_page_bytes)
        if self._injector is not None and getattr(lm, "paged", False) \
                and self.session.paged is not None:
            # allocator seam: forced PagePoolExhausted storms
            self.session.paged.allocator.fault_hook = self._injector.on_alloc
            if self.session.paged.tier is not None:
                # tier seam: seeded restore failures / corrupted tier bytes
                self.session.paged.tier.fault_hook = \
                    self._injector.on_tier_restore
        # durable park tier (inference/conversation_tier.py): idle
        # conversations spill KV pages + request state to the checkpoint
        # storage backends and evict entirely from device AND host. The
        # store may be shared fleet-wide (Router passes park_store) so a
        # conversation parked by a drained/crashed replica resumes anywhere.
        self.park_idle_blocks = int(park_idle_blocks)
        self.park_store = None
        if park_store is not None or park_dir is not None:
            if park_store is not None:
                self.park_store = park_store
            else:
                from neuronx_distributed_tpu.inference.conversation_tier \
                    import ConversationParkStore
                self.park_store = ConversationParkStore(park_dir)
            if self._injector is not None:
                # park seam: seeded write failures / torn manifests / read
                # failures / at-rest bit flips (one draw per operation)
                self.park_store.write_fault_hook = self._injector.on_park_write
                self.park_store.read_fault_hook = self._injector.on_park_read
        # in-process records of parked conversations (request object +
        # generated tokens + wall stamps): the degradation ladder's last
        # rung before "unresumable", and the snapshot's parked section
        self._parked: Dict[int, dict] = {}
        # rid -> block it (re)entered decode: the idle sweep's clock
        self._decode_since: Dict[int, int] = {}
        b = lm.max_batch
        # heap-backed admission backlog (inference/schedq.py): EDF order,
        # shed victims, queued-deadline expiry and the arrived/token
        # counters are all O(log n) / O(1) instead of per-block re-sorts
        # and linear scans (ROADMAP #18)
        self.queue: AdmissionQueue = AdmissionQueue()
        self.slots: List[Optional[Request]] = [None] * b
        self._out: Dict[int, List[int]] = {}
        self._out_ts: Dict[int, List[float]] = {}
        # keep_completions=False bounds host memory on long soaks: finished
        # streams fold into the stats counters + latency histograms (the
        # streaming-report surface) instead of growing this list
        self.keep_completions = bool(keep_completions)
        self.completed: List[Completion] = []
        self.rejected: List[Rejected] = []
        # request ids that received tokens THIS block — the router's
        # delivery-record refresh reads only these instead of rebuilding
        # every in-flight stream's record per block (ISSUE 14 satellite)
        self._emitted: set = set()
        # in-flight recovery work: (request, generated-so-far, token stamps)
        # awaiting a replay re-prefill (crash restore / corrupted-page
        # recovery); drained before admission each block
        self._replay_q: deque[Tuple[Request, List[int], List[float]]] = deque()
        self._replay_tokens = 0     # sum max_new_tokens over _replay_q
        # host mirrors of the on-device per-slot state (exact by design:
        # every device latch is a pure function of the fetched emissions)
        self._lengths = np.zeros((b,), np.int32)
        self._active = np.zeros((b,), bool)
        self._done = np.zeros((b,), bool)
        self._eos = np.full((b,), -1, np.int32)
        self._temp = np.zeros((b,), np.float32)
        self._greedy = np.ones((b,), bool)
        self._tok = np.zeros((b,), np.int32)
        # per-slot generated-token counters (the device samples row j's step
        # under fold_in(slot_keys[j], counts[j]); the request keys are the
        # session's, see _slot_keys)
        self._gen_counts = np.zeros((b,), np.int32)
        # where a sync launch packs the rows only the host writes
        self._block_rows = CausalLM.block_rows(
            self._gen_counts, self._lengths, self._active, self._eos,
            self._temp, self._greedy)
        # async pipeline state (async_loop=True): at most ONE in-flight
        # dispatched-but-unfetched block record rides _inflight between
        # iterations (deque so a flush drains in dispatch order); _staged
        # maps slots admitted/adopted/replayed since the previous dispatch to
        # their next-dispatch input overrides (None = read the host mirrors,
        # a dict = deferred device values, see _dispatch_block_async);
        # _first_pending holds deferred first-token records whose sampler
        # output was left on device so admission never blocks the pipeline.
        self._inflight: deque = deque()
        self._staged: Dict[int, Optional[dict]] = {}
        self._first_pending: List[dict] = []
        # chunked-prefill state: slot -> in-flight admission, FIFO order
        self._prefilling: Dict[int, _PrefillInFlight] = {}
        self._prefill_q: deque[int] = deque()
        self._next_id = 0
        self.blocks = 0
        # the virtual block the last step_block() entered on, and the
        # pipeline depth at that entry — load_summary stamps signal
        # freshness from THESE, not self.blocks, because an idle sync step
        # returns without advancing the clock (virtual time only moves
        # when there is work) while its summary is fully current, and an
        # async drain step that harvests the last in-flight block still
        # only reflects device effects through the PREVIOUS block
        self._observed_pin = 0
        self._entry_inflight = 0
        # a traced round's stamp for its next phase to begin on: where its
        # step_block span began, then where its block's fetch ended
        self._tile_at: Optional[float] = None
        # paged mode (lm built with page_size): admission additionally
        # consults the prefix index + page allocator — a prefix hit prefills
        # only the suffix, pool pressure defers admission instead of OOMing
        self.paged = bool(getattr(lm, "paged", False))
        if self.paged and self.session.paged is not None:
            self.session.paged.attach_observability(
                self.tracer, self.metrics, block_fn=lambda: self.blocks)
            self._m_pool = self.metrics.gauge(
                "serve_page_pool_in_use", help="allocated KV pages")
        # multi-LoRA mode (lm built with lora_rank): admission keys on
        # (tenant, adapter) — loading/pinning the request's adapter in the
        # session's device-resident AdapterPool; retire unpins. The per-slot
        # adapter_idx array rides every dispatch next to eos/temperature.
        self.lora = bool(getattr(lm, "lora", False))
        self._adapter_idx = np.zeros((b,), np.int32)
        self._adapter_pins: Dict[int, str] = {}
        if self.lora:
            self.session.adapters.attach_observability(
                self.tracer, self.metrics, block_fn=lambda: self.blocks)
            if self._injector is not None:
                self.session.adapters.fault_hook = \
                    self._injector.on_adapter_acquire
        # structured-decoding mode (lm built with grammar_slots): admission
        # loads+pins the request's token-DFA tables in the session's
        # GrammarPool; the per-slot grammar_idx/dfa_state/token_budget
        # arrays ride every fused dispatch next to eos/temperature, and the
        # host mirrors the DFA walk from the fetched emissions (a pure
        # function of the emitted tokens — no extra host ops).
        self.grammar = bool(getattr(lm, "grammar", False))
        # where a fused block's sums start among its outputs: after the five
        # row outputs and the DFA state (compile_session_decode_fused)
        self._walked_at = 6 if self.grammar else 5
        self._gidx = np.zeros((b,), np.int32)
        self._gstate = np.zeros((b,), np.int32)
        self._gbudget = np.zeros((b,), np.int32)
        self._grammar_pins: Dict[int, str] = {}
        # finish_reason latches, keyed by request id ("eos" / "budget" /
        # "grammar_accept"); expiry/cancel override at completion time
        self._finish_reason: Dict[int, str] = {}
        if self.grammar:
            self.session.grammars.attach_observability(
                self.tracer, self.metrics, block_fn=lambda: self.blocks)
            if self._injector is not None:
                self.session.grammars.fault_hook = \
                    self._injector.on_grammar_acquire
        # legacy counter surface, now a registry-backed view (see _StatsView)
        self.stats = _StatsView(self.metrics, _STAT_KEYS)
        self._stream_mixes = int(getattr(lm.config, "stream_mixes", 0))
        for key in ("param_relaid_leaves", "param_relaid_bytes"):
            self.stats[key] = getattr(lm, key, 0)
        for key, value in _startup_stats().items():
            self.stats[key] = value

    # --- submission ------------------------------------------------------

    def register_adapter(self, name: str, lora_params, lora_config) -> None:
        """Register ``name``'s LoRA weights (an ``init_lora`` tree + its
        ``LoraConfig``) with the session's device-resident pool. Host-side
        only — the adapter becomes device-resident at the first admission
        that pins it (``submit(adapter=name)``)."""
        if not self.lora:
            raise ValueError(
                "register_adapter requires a CausalLM built with lora_rank")
        self.session.adapters.register(name, lora_params, lora_config)

    def _validate_adapter(self, adapter: Optional[str]) -> None:
        if adapter is None:
            return
        if not self.lora:
            raise ValueError(
                "submit(adapter=) requires a CausalLM built with lora_rank")
        if not self.session.adapters.registered(adapter):
            raise ValueError(
                f"unknown adapter {adapter!r} (register_adapter first)")

    def register_grammar(self, name: str, regex: Optional[str] = None,
                         json_schema: Optional[dict] = None) -> None:
        """Compile + register a grammar with the session's device-resident
        pool (host-side only — tables become device-resident at the first
        admission that pins them, ``submit(grammar=name)``). Raises
        :class:`~neuronx_distributed_tpu.inference.grammar.
        GrammarCompileError` on a bad pattern — rejection happens HERE (or
        at submit for budget/unknown-name errors), never after device
        work started."""
        if not self.grammar:
            raise ValueError(
                "register_grammar requires a CausalLM built with "
                "grammar_slots")
        self.session.grammars.register(name, regex=regex,
                                       json_schema=json_schema)

    def _validate_grammar(self, grammar: Optional[str],
                          max_new_tokens: int) -> None:
        if grammar is None:
            return
        if not self.grammar:
            raise ValueError(
                "submit(grammar=) requires a CausalLM built with "
                "grammar_slots")
        pool = self.session.grammars
        if not pool.registered(grammar):
            raise ValueError(
                f"unknown grammar {grammar!r} (register_grammar first)")
        need = pool.min_tokens(grammar)
        if max_new_tokens < need:
            raise ValueError(
                f"grammar {grammar!r} needs at least {need} tokens to reach "
                f"an accept state; max_new_tokens {max_new_tokens} could "
                f"never parse")

    def _validate_submit(self, prompt: np.ndarray, max_new_tokens: int,
                         sampler: Optional[Sampler]
                         ) -> Tuple[np.ndarray, Sampler, bool]:
        """Shared admission validation (used by :meth:`submit` and the
        Router, which builds its own :class:`Request`): prompt shape, cache
        room, bucket/chunk ceiling, pool feasibility, sampler compatibility.
        Returns the normalized (prompt, sampler, greedy) triple."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        room = self.lm.config.max_seq_len - 1  # step() guard: last slot unused
        if prompt.size + max_new_tokens > room:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds serveable cache room {room}")
        chunked = (self.prefill_chunk_tokens
                   and prompt.size > self.prefill_chunk_tokens)
        if prompt.size > self.lm.buckets[-1] and not chunked:
            # chunked admission lifts the bucket ceiling: each chunk rides
            # its own (<= prefill_chunk_tokens) bucket
            raise ValueError(
                f"prompt length {prompt.size} exceeds largest bucket "
                f"{self.lm.buckets[-1]}")
        if self.paged:
            pkv = self.session.paged
            need = pkv.pages_needed(prompt.size,
                                    max_new_tokens + self._reserve_slack())
            if need > pkv.capacity_pages():
                # reject now: a request no drained pool could ever hold
                # would otherwise deadlock the admission queue
                raise ValueError(
                    f"request needs {need} pages, pool holds at most "
                    f"{pkv.capacity_pages()}")
        sampler = sampler or Sampler(greedy=True)
        if (sampler.top_k, sampler.top_p) != (self.slot_sampler.top_k,
                                              self.slot_sampler.top_p):
            raise ValueError(
                f"request sampler top_k/top_p {sampler.top_k}/{sampler.top_p} "
                f"differ from the engine's compiled "
                f"{self.slot_sampler.top_k}/{self.slot_sampler.top_p}")
        greedy = bool(sampler.greedy or sampler.temperature == 0.0)
        return prompt, sampler, greedy

    def submit(self, prompt: Optional[np.ndarray] = None,
               max_new_tokens: int = 0,
               sampler: Optional[Sampler] = None,
               eos_token_id: Optional[int] = None,
               arrival_block: int = 0,
               ttft_deadline_ms: Optional[float] = None,
               deadline_ms: Optional[float] = None,
               tenant: str = "default",
               adapter: Optional[str] = None,
               grammar: Optional[str] = None,
               request_id: Optional[int] = None,
               resume: Optional[int] = None) -> Union[int, "Rejected"]:
        """Queue a request; returns its id — or, when the bounded queue
        sheds it at arrival, a structured :class:`Rejected` with a
        retry-after estimate. The per-request ``sampler`` must agree with
        the engine's static ``top_k``/``top_p`` (those are baked into the
        compiled program — a mismatch would silently sample a different
        distribution, so it is rejected here at admission).

        ``ttft_deadline_ms``/``deadline_ms`` are budgets RELATIVE TO ARRIVAL
        for the first token and the whole stream, converted to the virtual
        block clock at ``block_time_ms`` per block. A queued or mid-prefill
        request whose deadline passes is expired without burning prefill; a
        decoding request past ``deadline_ms`` retires at the next block
        boundary with a partial ``expired=True`` completion.

        ``request_id`` pins an external id (the Router's globally-unique
        ids) instead of the engine's own counter: the per-request rng
        contract keys streams on the id, so a request replayed on another
        replica under the same id is bit-identical wherever it runs.

        ``resume`` is the conversation tier's re-entry point (the next user
        turn of a parked session): ``submit(resume=rid)`` takes no prompt —
        the durable park record carries the whole request — and delegates
        to :meth:`resume_parked` (exact page re-adoption, or re-prefill on
        any degradation — never a wrong token)."""
        if resume is not None:
            if prompt is not None:
                raise ValueError(
                    "submit(resume=rid) takes no prompt — the parked "
                    "record carries the request")
            return self.resume_parked(int(resume))
        if prompt is None:
            raise ValueError("prompt required (or pass resume=<parked id>)")
        prompt, sampler, greedy = self._validate_submit(
            prompt, max_new_tokens, sampler)
        self._validate_adapter(adapter)
        self._validate_grammar(grammar, int(max_new_tokens))
        rid = self._next_id if request_id is None else int(request_id)
        req = Request(
            request_id=rid, prompt=prompt,
            max_new_tokens=int(max_new_tokens), eos_token_id=eos_token_id,
            temperature=0.0 if greedy else float(sampler.temperature),
            greedy=greedy, arrival_block=int(arrival_block),
            submit_block=self.blocks,
            ttft_deadline_block=self._deadline_block(
                arrival_block, ttft_deadline_ms, "ttft_deadline_ms"),
            deadline_block=self._deadline_block(
                arrival_block, deadline_ms, "deadline_ms"),
            tenant=str(tenant),
            adapter=adapter,
            grammar=grammar,
        )
        return self.submit_request(req)

    def submit_request(self, req: Request) -> Union[int, "Rejected"]:
        """Queue an already-validated :class:`Request` (the Router's
        placement path — deadlines arrive as ABSOLUTE blocks on the shared
        clock, so a router-queued wait never silently extends a budget)."""
        if self.role == "decode":
            raise ValueError(
                "a decode worker admits streams via adopt_handoff/resume "
                "only — fresh work goes to a prefill worker")
        self._next_id = max(self._next_id, req.request_id + 1)
        now = time.perf_counter()
        self._submit_ts[req.request_id] = now
        if self.tracer.enabled:
            self.tracer.instant(
                "submit", ("req", req.request_id), block=self.blocks,
                ts=now,
                args={"prompt_len": int(req.prompt.size),
                      "max_new_tokens": int(req.max_new_tokens),
                      "arrival_block": int(req.arrival_block),
                      "ttft_deadline_block": req.ttft_deadline_block,
                      "deadline_block": req.deadline_block,
                      "tenant": req.tenant,
                      "adapter": req.adapter,
                      "grammar": req.grammar,
                      "engine": self.lane})
        # bound the ARRIVED backlog at submit time (the live-client path);
        # future-arrival submissions are scheduled arrivals, not queue
        # pressure — they are shed at the block boundary where they arrive
        # into an already-full queue (_shed_overflow). Free slots extend the
        # limit (a request the next round admits immediately is not
        # backlog) — but only slots the PAGE POOL could actually fill: under
        # pool exhaustion a free slot admits nothing, so it must not excuse
        # unbounded queueing (the rejection then says so, with a retry-after
        # read off the oldest decoding stream's remaining budget — the
        # earliest retirement that returns pages).
        if self.max_queue is not None and req.arrival_block <= self.blocks:
            arrived = self.queue.arrived_count(self.blocks)
            pool_bound = not self._pool_can_admit(req.prompt.size,
                                                  req.max_new_tokens)
            usable = 0 if pool_bound else len(self._free_slots())
            if arrived >= self.max_queue + usable:
                return self._shed(req, pool_bound=pool_bound)
        self.queue.append(req)
        self._m_queue.set(len(self.queue))
        return req.request_id

    def cancel(self, request_id: int) -> bool:
        """Retire a request in whatever state it is in (client disconnect):
        queued → dropped; mid-chunked-prefill → slot freed, pages rolled
        back atomically, no completion; decoding → retired NOW with a
        partial (``cancelled=True``) completion. Returns False when the id
        is unknown or already completed."""
        r = self.queue.find(request_id)
        if r is not None:
            self.queue.remove(request_id)
            self._release_adapter(r)
            self._release_grammar(r)
            self.stats["cancelled"] += 1
            if self.tracer.enabled:
                self.tracer.instant("cancel", ("req", request_id),
                                    block=self.blocks,
                                    args={"state": "queued"})
            return True
        for i, (req, pregen, ts) in enumerate(self._replay_q):
            if req.request_id == request_id:
                del self._replay_q[i]
                self._replay_tokens -= req.max_new_tokens
                # the client already HAS pregen tokens; the completion
                # records them so accounting stays whole-stream
                self._out[req.request_id] = list(pregen)
                self._out_ts[req.request_id] = list(ts)
                self._emit_completion(self._completion_of(
                    req, cancelled=True))
                self.stats["cancelled"] += 1
                return True
        for slot, st in list(self._prefilling.items()):
            if st.req.request_id == request_id:
                self._abort_prefill(slot, requeue=False)
                self._release_adapter(st.req)
                self._release_grammar(st.req)
                self.stats["cancelled"] += 1
                if self.tracer.enabled:
                    self.tracer.instant("cancel", ("req", request_id),
                                        block=self.blocks,
                                        args={"state": "prefill"})
                return True
        if any(p["rid"] == request_id for p in self._first_pending):
            # its first token is still on the device: settle (what was
            # dispatched for the client is recorded). A one-token request
            # that released its slot at dispatch completes here, as it would
            # have inside its round, and the cancel finds nothing to cut
            self._flush()
        for slot, req in enumerate(self.slots):
            if req is not None and req.request_id == request_id:
                # async: the in-flight block still includes this row; drain
                # it (recording its deliveries — the client had them coming)
                # before the partial completion is cut. The drain may reveal
                # the stream already finished — then it completes normally
                # (exactly what the sync loop would have delivered) and the
                # cancel finds nothing to cut.
                if self.async_loop:
                    self._flush()
                    self._retire_finished()
                    if self.slots[slot] is not req:
                        return False
                self.lm.retire(self.session, np.asarray([slot], np.int32))
                self._complete_slot(slot, cancelled=True)
                self.stats["cancelled"] += 1
                return True
        return False

    # --- scheduling internals -------------------------------------------

    def _req_key(self, request_id: int) -> jax.Array:
        return jax.random.fold_in(self.rng, request_id)

    # the per-slot request keys live with the session: an insert program
    # writes its rows' entries on the device (CausalLM._first_token)
    @property
    def _slot_keys(self) -> Optional[jax.Array]:
        return getattr(self.session, "slot_keys", None)

    @_slot_keys.setter
    def _slot_keys(self, keys: jax.Array) -> None:
        self.session.slot_keys = keys

    def _first_inputs(self, group: Sequence[Request]) -> Optional[FirstToken]:
        """What the insert program samples ``group``'s first tokens with:
        each request's key stream (token index 0 of it), its sampler knobs
        and, in an engine built with grammars, the budget-aware mask of each
        grammar's START state. Host arrays; None in simulation."""
        if self._sim:
            return None
        n = len(group)
        return FirstToken(
            self.rng, np.asarray([r.request_id for r in group], np.uint32),
            np.asarray([r.temperature for r in group], np.float32),
            np.asarray([r.greedy for r in group], bool),
            sampler=self.slot_sampler,
            allowed=self._grammar_allowed_rows(group, [0] * n, [0] * n))

    def _fetch_first(self, first_dev, routing) -> np.ndarray:
        """An insert's ONE fetch: its sampled first tokens and ``routing``,
        the pair ``(routing sums of a model with experts, scan sums of a model
        with per-slot state)`` (counted here; either None), together."""
        t0 = time.perf_counter()
        first, (sums, scanned) = jax.device_get((first_dev, routing or (None, None)))
        if self.tracer.enabled:
            self.tracer.complete("insert_fetch", (self.lane, "dispatch"), t0,
                                 time.perf_counter(), block=self.blocks)
        self.stats["insert_host_fetches"] += 1
        if sums is not None:
            self._count_insert_routing(sums)
        if scanned is not None:
            self.stats["ssm_scan_tokens"] += int(scanned[0])
            self.stats["ssm_scan_positions"] += int(scanned[1])
        return first

    def _count_insert_program(self) -> None:
        """One compiled call that admitted requests (an insert, a chunk's
        extend) was dispatched; for a model with several residual streams,
        what it ran over (``session.insert_ran``: host numbers)."""
        self.stats["insert_program_calls"] += 1
        if self._sim:
            return
        self.stats["insert_programs_fresh"] += int(self.session.insert_fresh)
        if self._stream_mixes:
            tokens, slots = self.session.insert_ran
            self.stats["mhc_mix_tokens"] += self._stream_mixes * tokens
            self.stats["mhc_mix_slots"] += self._stream_mixes * slots

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    # --- adapter admission (multi-LoRA) ----------------------------------

    def _acquire_adapter(self, req: Request) -> bool:
        """Load + pin the request's adapter at admission time (no-op for
        base requests, or when a requeued admission's pin survived). False
        means the request did NOT admit this round:

        * :class:`AdapterPoolExhausted` — every slot pinned, nothing
          evictable: the request is shed with a structured
          ``Rejected(reason="adapter_pool_exhausted")`` (pins return as
          streams retire — the retry-after says when);
        * :class:`AdapterLoadError` (the seeded ``adapter`` fault seam) —
          requeued for a later block: a deterministic retry, NEVER a
          silent wrong-adapter token.
        """
        if req.adapter is None or not self.lora:
            return True
        if req.request_id in self._adapter_pins:
            return True
        pool = self.session.adapters
        loads_before = pool.stats["loads"]
        try:
            slot = pool.acquire(req.adapter)
        except AdapterPoolExhausted:
            rej = Rejected(
                request_id=req.request_id,
                retry_after_blocks=self._pool_retry_after(),
                queue_depth=sum(1 for r in self.queue
                                if r.arrival_block <= self.blocks),
                reason="adapter_pool_exhausted")
            self.rejected.append(rej)
            self.stats["rejected"] += 1
            self.stats["adapter_rejects"] += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "shed", ("req", req.request_id), block=self.blocks,
                    args={"reason": rej.reason, "adapter": req.adapter,
                          "retry_after_blocks": rej.retry_after_blocks})
            return False
        except AdapterLoadError as e:
            self.stats["adapter_load_retries"] += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "adapter_defer", ("req", req.request_id),
                    block=self.blocks,
                    args={"adapter": req.adapter, "error": str(e)})
            self.queue.appendleft(req)
            return False
        self._adapter_pins[req.request_id] = req.adapter
        if self.tracer.enabled:
            # the adapter-load mark inside admission: request_timeline and
            # the attribution annotations read it off the request lane
            self.tracer.instant(
                "adapter_load", ("req", req.request_id), block=self.blocks,
                args={"adapter": req.adapter, "slot": int(slot),
                      "cold": pool.stats["loads"] > loads_before})
        return True

    def _adapter_slot(self, req: Request) -> int:
        if req.adapter is None or not self.lora:
            return 0
        return self.session.adapters.slot_of(req.adapter)

    def _release_adapter(self, req: Request) -> None:
        name = self._adapter_pins.pop(req.request_id, None)
        if name is not None:
            self.session.adapters.release(name)

    # --- grammar admission (structured decoding) -------------------------

    def _acquire_grammar(self, req: Request) -> bool:
        """Load + pin the request's grammar tables at admission time (no-op
        for free-form requests, or when a requeued admission's pin
        survived) — the ``_acquire_adapter`` contract: False means the
        request did NOT admit this round (shed with
        ``Rejected(reason="grammar_pool_exhausted")``, or requeued on an
        injected :class:`GrammarLoadError`)."""
        if req.grammar is None or not self.grammar:
            return True
        if req.request_id in self._grammar_pins:
            return True
        pool = self.session.grammars
        loads_before = pool.stats["loads"]
        try:
            slot = pool.acquire(req.grammar)
        except GrammarPoolExhausted:
            rej = Rejected(
                request_id=req.request_id,
                retry_after_blocks=self._pool_retry_after(),
                queue_depth=sum(1 for r in self.queue
                                if r.arrival_block <= self.blocks),
                reason="grammar_pool_exhausted")
            self.rejected.append(rej)
            self.stats["rejected"] += 1
            self.stats["grammar_rejects"] += 1
            self._release_adapter(req)   # the group-mate pin goes too
            if self.tracer.enabled:
                self.tracer.instant(
                    "shed", ("req", req.request_id), block=self.blocks,
                    args={"reason": rej.reason, "grammar": req.grammar,
                          "retry_after_blocks": rej.retry_after_blocks})
            return False
        except GrammarLoadError as e:
            self.stats["grammar_load_retries"] += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "grammar_defer", ("req", req.request_id),
                    block=self.blocks,
                    args={"grammar": req.grammar, "error": str(e)})
            self.queue.appendleft(req)
            return False
        self._grammar_pins[req.request_id] = req.grammar
        if self.tracer.enabled:
            self.tracer.instant(
                "grammar_load", ("req", req.request_id), block=self.blocks,
                args={"grammar": req.grammar, "slot": int(slot),
                      "cold": pool.stats["loads"] > loads_before})
        return True

    def _grammar_slot(self, req: Request) -> int:
        if req.grammar is None or not self.grammar:
            return 0
        return self.session.grammars.slot_of(req.grammar)

    def _release_grammar(self, req: Request) -> None:
        name = self._grammar_pins.pop(req.request_id, None)
        if name is not None:
            self.session.grammars.release(name)

    def _grammar_walk(self, name: str, state: int,
                      tokens: Sequence[int]) -> int:
        """Host-side DFA walk (registry tables) — the replay/adoption path
        restoring a resumed stream's state from its delivered tokens."""
        dfa = self.session.grammars.grammar(name)
        for t in tokens:
            state = dfa.walk(state, int(t))
            if state < 0:
                raise ValueError(
                    f"delivered token {int(t)} violates grammar {name!r} — "
                    f"the recovery record is corrupt")
        return state

    def _advance_grammar(self, slot: int, token: int) -> None:
        """Mirror the device's DFA transition for one EMITTED token of a
        live grammar slot: step the host state, and latch ``done`` (+
        ``finish_reason="grammar_accept"``) on an accept-terminal landing
        — the grammar's EOS. A pure function of the fetched emissions, so
        the mirror costs no extra host ops."""
        if not self.grammar or self._gidx[slot] == 0:
            return
        req = self.slots[slot]
        if req is None:
            return
        dfa = self.session.grammars.grammar(req.grammar)
        nxt = dfa.walk(int(self._gstate[slot]), int(token))
        if nxt < 0:
            # unreachable for active rows (the mask forbids it); keep the
            # frozen state for done rows whose raw sample wandered
            return
        self._gstate[slot] = nxt
        if dfa.terminal[nxt]:
            self._done[slot] = True
            if self._finish_reason.get(req.request_id) != "eos":
                self._finish_reason[req.request_id] = "grammar_accept"

    def _grammar_allowed_rows(self, reqs: Sequence[Request],
                              states: Sequence[int],
                              counts: Sequence[int]):
        """Host-side (rows, vocab) budget-aware allowed mask for a
        first-token sampling site — None when no row is constrained (the
        sampler path stays byte-identical to a grammarless engine). The
        boolean math is :meth:`CausalLM.grammar_allowed` run on the host
        registry tables, so host and device masks agree exactly."""
        if not self.grammar or all(r.grammar is None for r in reqs):
            return None
        pool = self.session.grammars
        rows = []
        for r, st, ct in zip(reqs, states, counts):
            if r.grammar is None:
                rows.append(np.ones((pool.vocab,), bool))
            else:
                dfa = pool.grammar(r.grammar)
                rows.append(dfa.allowed_row(
                    int(st), int(r.max_new_tokens) - int(ct) - 1))
        return np.stack(rows)

    @staticmethod
    def _mask_logits(logits, allowed):
        """Pre-mask first-token logits on the HOST (numpy) when a group
        carries constrained rows: the sampler then runs its ordinary
        unmasked path, so masked admissions add ZERO new eager-op shapes
        over a grammarless engine (first-call eager compiles would
        otherwise land inside measured serving windows). Bit-identical to
        the in-sampler ``where``: both select the same float values."""
        if allowed is None:
            return logits
        return jnp.asarray(np.where(
            allowed, np.asarray(logits, np.float32), np.float32(-1e30)))

    # --- deadlines / shedding / dispatch (the fault-tolerance half) ------

    def _deadline_block(self, arrival_block: int, ms: Optional[float],
                        name: str) -> Optional[int]:
        if ms is None:
            return None
        if ms <= 0:
            raise ValueError(f"{name} must be > 0, got {ms}")
        return int(arrival_block) + max(
            1, int(np.ceil(float(ms) / self.block_time_ms)))

    # EDF / shed victim orderings live in inference/schedq.py now (the
    # heaps and the engine must share one definition); kept as staticmethod
    # aliases for the tests and external callers that pinned them
    _admission_deadline = staticmethod(admission_deadline)
    _shed_key = staticmethod(shed_deadline_key)

    def _deadline_passed(self, r: Request) -> bool:
        return ((r.ttft_deadline_block is not None
                 and self.blocks > r.ttft_deadline_block)
                or (r.deadline_block is not None
                    and self.blocks > r.deadline_block))

    def _missed(self, req: Request, at: Optional[int] = None) -> bool:
        if req.ttft_deadline_block is not None and (
                req.first_token_block is None
                or req.first_token_block > req.ttft_deadline_block):
            return True
        return (req.deadline_block is not None
                and (self.blocks if at is None else at) > req.deadline_block)

    def _retry_after(self) -> int:
        """Backlog-drain estimate in blocks: total undelivered token budget
        (queued + replaying + in-flight remainders) over the pool's K*slots
        per-block service rate — what a shed client should wait before
        resubmitting."""
        queued = self.queue.tokens() + self._replay_tokens
        inflight = sum(
            req.max_new_tokens - len(self._out.get(req.request_id, []))
            for req in self.slots if req is not None)
        rate = max(self.lm.max_batch * self.block_steps, 1)
        return max(1, -(-(queued + inflight) // rate))

    def _reserve_slack(self) -> int:
        """Decode-overrun page reserve beyond ``max_new_tokens``. The sync
        loop retires a finished row at the block boundary its EOS/budget
        latch was fetched, so a row writes at most ``block_steps - 1`` cache
        positions past its last delivered token. The async pipeline learns
        the latch one block LATER (block t's fetch lands while t+1 runs),
        so a finished row rides exactly one extra dispatched block before
        retire — double the reserve. Same safety argument as sync: the
        over-written positions are covered by reserved pages the slot owns
        and retire's scratch-table reset unmaps them before reuse."""
        return self.block_steps * 2 if self.async_loop else self.block_steps

    def _pool_can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Whether the page pool could cover this admission RIGHT NOW
        (free pages plus whatever reclaim — tier spill of cache-only pages,
        else LRU drop — would return). Contiguous engines always can —
        their slots ARE the capacity."""
        if not self.paged:
            return True
        pkv = self.session.paged
        # a prefill worker never decodes: its footprint is the prompt pages
        # only (the decode reserve is the ADOPTING worker's cost)
        need = pkv.pages_needed(prompt_len,
                                0 if self.role == "prefill"
                                else max_new_tokens + self._reserve_slack())
        free = pkv.allocator.available()
        if free < need and pkv.prefix is not None:
            free += pkv.prefix.reclaimable_pages()
        return free >= need

    def _pool_retry_after(self, req: Optional[Request] = None) -> int:
        """Pool-pressure retry estimate, two branches (ISSUE 8 satellite):

        * a SPILL could free enough pages for ``req`` — the shortfall is
          cold cache-resident pages the tier can absorb, which the very
          next admission attempt reclaims: retry after ~1 block (spill
          latency), NOT the oldest stream's remaining budget;
        * otherwise the OLDEST decoding request's remaining token budget in
          blocks — the earliest retirement that returns pages to the pool.
        """
        pkv = self.session.paged if self.paged else None
        if (req is not None and pkv is not None and pkv.prefix is not None
                and pkv.tier is not None):
            need = pkv.pages_needed(req.prompt.size,
                                    req.max_new_tokens + self._reserve_slack())
            if (pkv.allocator.available()
                    + pkv.prefix.spillable_pages()) >= need:
                return 1
        oldest: Optional[Request] = None
        for slot, req_ in enumerate(self.slots):
            if req_ is None or slot in self._prefilling:
                continue
            if oldest is None or ((req_.start_block or 0)
                                  < (oldest.start_block or 0)):
                oldest = req_
        if oldest is None:
            return 1
        remaining = (oldest.max_new_tokens
                     - len(self._out.get(oldest.request_id, [])))
        return max(1, -(-remaining // self.block_steps))

    def _note_pool_pressure(self, reqs: Sequence[Request]) -> None:
        """One pool-pressure episode: marks the block for the incident
        recorder's storm detector and stamps a per-request ``pool_defer``
        instant on each deferred request's lane — the attribution layer's
        'pool_wait' phase boundary (a deferral otherwise looks like plain
        queueing)."""
        if self.incident is not None:
            self._pool_pressure_blocks.append(self.blocks)
        if self.tracer.enabled:
            for r in reqs:
                self.tracer.instant(
                    "pool_defer", ("req", r.request_id), block=self.blocks,
                    args={"free_pages": (
                        self.session.paged.allocator.available()
                        if self.session.paged is not None else None)})

    def _shed(self, req: Request,
              pool_bound: bool = False) -> Union[int, Rejected]:
        """Shed on an over-full arrived backlog: 'tail' rejects the
        newcomer; 'deadline' rejects whichever of queue+newcomer has the
        laxest deadline (the newcomer may displace a queued request, which
        then surfaces in ``self.rejected``). ``pool_bound`` marks a shed
        forced by page-pool exhaustion rather than queue depth: the reason
        says so and the retry-after is read off the oldest decoding
        stream's remaining budget instead of the queue-drain rate."""
        victim = req
        if self.shed_policy == "deadline":
            worst = self.queue.peek_lax_victim(self.blocks)
            if (worst is not None
                    and shed_deadline_key(worst) > shed_deadline_key(req)):
                self.queue.remove(worst.request_id)
                self.queue.append(req)
                victim = worst
                self.stats["shed_evictions"] += 1
        retry = self._retry_after()
        if pool_bound:
            retry = max(retry, self._pool_retry_after(victim))
            if self.incident is not None:
                self._pool_pressure_blocks.append(self.blocks)
        self._release_adapter(victim)
        self._release_grammar(victim)
        rej = Rejected(request_id=victim.request_id,
                       retry_after_blocks=retry,
                       queue_depth=self.queue.arrived_count(self.blocks),
                       reason="pool_exhausted" if pool_bound
                       else "queue_full")
        self.rejected.append(rej)
        self.stats["rejected"] += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "shed", ("req", victim.request_id), block=self.blocks,
                args={"policy": self.shed_policy,
                      "reason": rej.reason,
                      "retry_after_blocks": rej.retry_after_blocks,
                      "queue_depth": rej.queue_depth})
        return rej if victim is req else req.request_id

    def _shed_overflow(self) -> None:
        """Block-boundary backlog bound: requests submitted with future
        arrival blocks 'arrive' here — any overflow past ``max_queue`` is
        shed by policy, exactly like a live submit into a full queue. Runs
        AFTER the admission loop, so only requests that genuinely could not
        be placed count as backlog (leftover free slots — pool-pressure
        deferrals — extend the limit rather than shed waiting work)."""
        if self.max_queue is None:
            return
        limit = self.max_queue + len(self._free_slots())
        while True:
            arrived = self.queue.arrived_count(self.blocks)
            if arrived <= limit:
                return
            if self.shed_policy == "deadline":
                victim = self.queue.peek_lax_victim(self.blocks)
            else:
                victim = self.queue.peek_tail_victim(self.blocks)
            if victim is None:
                return
            self.queue.remove(victim.request_id)
            self._release_adapter(victim)
            self._release_grammar(victim)
            self.rejected.append(Rejected(
                request_id=victim.request_id,
                retry_after_blocks=self._retry_after(),
                queue_depth=arrived - 1))
            self.stats["rejected"] += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "shed", ("req", victim.request_id), block=self.blocks,
                    args={"policy": self.shed_policy, "at": "block_boundary",
                          "queue_depth": arrived - 1})

    def _dispatch(self, kind: str, fn):
        """Run one compiled-program dispatch with transient-failure
        retry+exponential backoff. The fault injector (when armed) raises
        BEFORE ``fn`` executes, so a retried dispatch never re-runs device
        work; past the retry budget the failure escalates to
        :class:`DispatchFailed` (fail-stop — snapshot/restore recovers).

        This is also the dispatch-latency observation point: every
        successful dispatch lands in the ``serve_dispatch_ms{kind=...}``
        histogram and (when tracing) an X span on the engine dispatch lane
        with its retry count; each injected/transient failure is an instant
        on the faults lane."""
        attempts = 0
        hist = self._disp_hist.get(kind)
        if hist is None:
            hist = self._disp_hist[kind] = self.metrics.histogram(
                "serve_dispatch_ms", help="compiled-program dispatch wall ms",
                kind=kind)
        while True:
            try:
                if self._injector is not None:
                    self._injector.before_dispatch(kind)
                t0 = time.perf_counter()
                out = fn()
                t1 = time.perf_counter()
                hist.observe((t1 - t0) * 1e3)
                if self.tracer.enabled:
                    self.tracer.complete(
                        kind, (self.lane, "dispatch"), t0, t1,
                        block=self.blocks,
                        args={"retries": attempts} if attempts else None)
                return out
            except TransientDispatchError as e:
                attempts += 1
                self.stats["dispatch_retries"] += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "fault:dispatch", (self.lane, "faults"),
                        block=self.blocks,
                        args={"kind": kind, "attempt": attempts,
                              "error": str(e)})
                if attempts > self.dispatch_retries:
                    if self.incident is not None:
                        self.incident.trigger(
                            "dispatch_failstop", self.blocks,
                            details={"kind": kind, "attempts": attempts,
                                     "error": str(e)},
                            state=self.state_summary())
                    raise DispatchFailed(
                        f"{kind} dispatch failed {attempts} times "
                        f"(retry budget {self.dispatch_retries})") from e
                delay = self.dispatch_backoff_s * (2 ** (attempts - 1))
                if delay > 0:
                    time.sleep(delay)

    def _completion_of(self, req: Request, cancelled: bool = False,
                       expired: bool = False,
                       block: Optional[int] = None) -> Completion:
        """``req``'s completion as of virtual block ``block`` (now, unless a
        reply fetched late names the block its stream ended on)."""
        at = self.blocks if block is None else block
        ts = self._out_ts.pop(req.request_id, [])
        self._submit_ts.pop(req.request_id, None)
        self._last_tok_ts.pop(req.request_id, None)
        self._decode_since.pop(req.request_id, None)
        self._release_adapter(req)   # retire unpins (adapter stays resident)
        self._release_grammar(req)   # ... and the grammar pin likewise
        missed = expired or self._missed(req, at)
        if self.incident is not None and missed:
            self._miss_blocks.append(at)
        if self.tracer.enabled:
            kind = ("cancel" if cancelled else
                    "expire" if expired else "retire")
            self.tracer.instant(
                kind, ("req", req.request_id), block=at,
                args={"generated": len(self._out.get(req.request_id, [])),
                      "deadline_missed": bool(missed)})
        reason = self._finish_reason.pop(req.request_id, "budget")
        if cancelled:
            reason = "cancelled"
        elif expired:
            reason = "expired"
        return Completion(
            request_id=req.request_id,
            tokens=np.asarray(self._out.pop(req.request_id, []), np.int64),
            prompt_len=req.prompt.size,
            queue_blocks=max((req.start_block
                              if req.start_block is not None else at)
                             - req.arrival_block, 0),
            decode_blocks=at - (req.start_block or 0),
            ttft_blocks=max((req.first_token_block
                             if req.first_token_block is not None
                             else at) - req.arrival_block, 0),
            token_ts=np.asarray(ts, np.float64),
            cancelled=cancelled, expired=expired,
            deadline_missed=missed,
            tenant=req.tenant,
            adapter=req.adapter,
            grammar=req.grammar,
            finish_reason=reason,
        )

    def _emit_completion(self, comp: Completion) -> None:
        """Single exit point for finished streams: folds the completion
        into the aggregate counters (the streaming report's source) and
        retains the object only when ``keep_completions`` — a 1M-request
        soak holds O(in-flight) completions instead of O(trace)."""
        self.stats["completed"] += 1
        self.stats["generated_tokens"] += len(comp.tokens)
        self.stats["queue_blocks_sum"] += comp.queue_blocks
        self.stats["ttft_blocks_sum"] += comp.ttft_blocks
        if comp.deadline_missed:
            self.stats["deadline_misses"] += 1
        if not (comp.deadline_missed or comp.expired or comp.cancelled):
            self.stats["ontime_tokens"] += len(comp.tokens)
        if self.keep_completions:
            self.completed.append(comp)

    def _complete_slot(self, slot: int, cancelled: bool = False,
                       expired: bool = False) -> None:
        req = self.slots[slot]
        self._emit_completion(self._completion_of(req, cancelled=cancelled,
                                                  expired=expired))
        self.slots[slot] = None
        self._active[slot] = False
        self._done[slot] = False
        self._adapter_idx[slot] = 0
        self._gidx[slot] = 0
        self._gstate[slot] = 0
        # async: a retired slot's next-dispatch override is void (a reused
        # slot gets a fresh one at its own admission); in-flight blocks that
        # still include this row are rid-gated at harvest
        self._staged.pop(slot, None)

    def _trace_queued(self, req: Request, now: float) -> None:
        """Close the request's 'queued' lifecycle span (submit wall stamp ->
        the moment a slot claimed it)."""
        if not self.tracer.enabled:
            return
        sts = self._submit_ts.get(req.request_id, now)
        self.tracer.complete(
            "queued", ("req", req.request_id), sts, now, block=self.blocks,
            args={"queue_blocks": max(self.blocks - req.arrival_block, 0)})

    def _observe_first_token(self, req: Request, slot: int, now: float,
                             claimed: Optional[float] = None,
                             block: Optional[int] = None,
                             **extra) -> None:
        """First-token observation shared by the admission paths (one-shot
        insert, chunked-prefill finish, fresh recovery replay): wall-TTFT
        histogram + admit/first_token marks on the request lane. ``admit``
        is stamped ``claimed`` where the caller took one when it claimed
        the slot (a one-shot insert: the prefill lies between the two
        marks), else ``now``. ``now`` is when the token reached the host: a
        deferred first token is observed where it is settled, under the
        ``block`` that admitted it."""
        sts = self._submit_ts.get(req.request_id)
        if sts is not None:
            self._m_ttft.observe((now - sts) * 1e3)
        if not self.tracer.enabled:
            return
        rid = req.request_id
        at = self.blocks if block is None else block
        self.tracer.instant(
            "admit", ("req", rid), ts=now if claimed is None else claimed,
            block=at,
            args={"slot": int(slot),
                  **{k: v for k, v in extra.items() if v is not None}})
        self.tracer.instant("first_token", ("req", rid), ts=now,
                            block=at,
                            args={"ttft_blocks": max(
                                at - req.arrival_block, 0)})

    def _expire_request(self, req: Request) -> None:
        """Deadline passed before (or while) prefill: deliver an empty
        ``expired`` completion — the client learns NOW instead of after
        wasted prefill + decode."""
        self._out.pop(req.request_id, None)
        self._out_ts.pop(req.request_id, None)
        self._submit_ts.pop(req.request_id, None)
        self._last_tok_ts.pop(req.request_id, None)
        self._release_adapter(req)
        self._release_grammar(req)
        if self.incident is not None:
            self._miss_blocks.append(self.blocks)
        if self.tracer.enabled:
            self.tracer.instant(
                "expire", ("req", req.request_id), block=self.blocks,
                args={"generated": 0, "state": "pre_decode",
                      "deadline_missed": True})
        self._emit_completion(Completion(
            request_id=req.request_id, tokens=np.zeros((0,), np.int64),
            prompt_len=req.prompt.size,
            queue_blocks=max(self.blocks - req.arrival_block, 0),
            decode_blocks=0,
            ttft_blocks=max(self.blocks - req.arrival_block, 0),
            token_ts=np.zeros((0,), np.float64),
            expired=True, deadline_missed=True,
            tenant=req.tenant,
            adapter=req.adapter,
            grammar=req.grammar,
            finish_reason="expired",
        ))
        self._finish_reason.pop(req.request_id, None)
        self.stats["expired"] += 1

    def _expire_queued(self) -> None:
        # O(log n) per expiry off the deadline heap (was a full queue scan
        # per block); expire_due returns deque order, so multi-expiry
        # blocks record completions in the historic order
        for r in self.queue.expire_due(self.blocks):
            self._expire_request(r)

    def _expire_prefilling(self) -> None:
        """Mid-chunked-prefill expiry: the admission unwinds atomically
        (pages released, device table reset — the cancel machinery) and the
        request expires; spent chunk work is discarded."""
        for slot, st in list(self._prefilling.items()):
            if self._deadline_passed(st.req):
                self._abort_prefill(slot, requeue=False)
                self._expire_request(st.req)

    def _expire_decoding(self) -> None:
        """Completion-deadline expiry for live streams: retire NOW with the
        tokens delivered so far (partial, ``expired=True``).

        Async: the expiry DECISION is pure virtual-clock (identical either
        way), but the partial's content would be one block short while a
        block is in flight — so the first victim triggers a pipeline flush
        (rare, and exactly the designated-sync-point discipline), making the
        delivered partial bit-identical to the sync loop's."""
        victims = [
            slot for slot, req in enumerate(self.slots)
            if req is not None and slot not in self._prefilling
            and not self._done[slot]
            and req.deadline_block is not None
            and self.blocks > req.deadline_block]
        if not victims:
            return
        self._flush()
        for slot in victims:
            req = self.slots[slot]
            if req is None or self._done[slot]:
                continue     # the flush finished it — normal retire path
            self.lm.retire(self.session, np.asarray([slot], np.int32))
            self._complete_slot(slot, expired=True)
            self.stats["expired"] += 1

    def _is_chunked(self, req: Request) -> bool:
        return bool(self.prefill_chunk_tokens
                    and req.prompt.size > self.prefill_chunk_tokens)

    def _admit(self) -> None:
        """Admit arrived requests into free slots, batching prompts that
        share a prefill bucket into ONE right-sized insert. Admission order
        is deadline-aware (:meth:`_arrived_sorted`): the head request's
        bucket defines the group, and the scan stops at the first request
        with a different bucket or a long prompt (which takes the chunked
        path alone). Expired queued requests leave first (no prefill burned
        on a missed deadline); AFTER admission fills what it can, the
        leftover arrived backlog is bounded (``max_queue`` shedding)."""
        self._expire_queued()
        try:
            self._admit_loop()
        finally:
            self._shed_overflow()

    def _admit_loop(self) -> None:
        # requests whose adapter load faulted THIS pass sit out the rest of
        # it (they were requeued for a later block); without the set a
        # head-of-queue load fault would spin the admission loop forever
        deferred: set = set()
        while True:
            free = self._free_slots()
            if not free:
                return
            # admission order off the EDF heap: only the first len(free)
            # arrived candidates are ever inspected (group size is capped
            # by free slots), so the scan is O(slots log n) instead of the
            # old full-backlog re-sort per iteration
            order = self.queue.peek_edf(self.blocks, deferred, len(free))
            if not order:
                return
            head = order[0]
            if self._is_chunked(head):
                self.queue.remove(head.request_id)
                if not self._acquire_adapter(head):
                    deferred.add(head.request_id)
                    continue
                if not self._acquire_grammar(head):
                    deferred.add(head.request_id)
                    continue
                self._begin_chunked(head, free[0])
                continue
            bucket = self.lm._bucket_for(head.prompt.size)
            group: List[Request] = []
            for r in order:
                if (len(group) >= len(free) or self._is_chunked(r)
                        or self.lm._bucket_for(r.prompt.size) != bucket):
                    break
                group.append(r)
            for r in group:
                self.queue.remove(r.request_id)
            # (tenant, adapter)-keyed admission: each request's adapter is
            # loaded+pinned before any device work; a failed acquire drops
            # the request out of the group (shed or requeued) while its
            # groupmates still ride one right-sized insert
            admitted = []
            for r in group:
                if self._acquire_adapter(r) and self._acquire_grammar(r):
                    admitted.append(r)
                else:
                    deferred.add(r.request_id)
            group = admitted
            if not group:
                continue
            try:
                released = self._insert_group(group, free[: len(group)],
                                              bucket)
            except PagePoolExhausted:
                # pool pressure (paged mode): the group insert is atomic and
                # no device work ran (allocation precedes the program).
                # Requeue and retry at the next block boundary — in-flight
                # retirements return pages. Fall back to admitting the head
                # alone first: with nothing in flight a too-big group would
                # otherwise never shrink (submit() guarantees any single
                # request fits a drained pool, so the head always progresses
                # eventually).
                self.stats["deferred_admissions"] += 1
                self.queue.extendleft(reversed(group[1:]))
                self._note_pool_pressure(group[1:])
                try:
                    released = self._insert_group(group[:1], free[:1], bucket)
                except PagePoolExhausted:
                    self.queue.appendleft(group[0])
                    self._note_pool_pressure(group[:1])
                    return
            if released:
                # one-token rows of an insert nobody waits for: slot, pages
                # and table row go back now, for the next group of this loop
                self.lm.retire(self.session, np.asarray(released, np.int32))
                self.stats["slots_released_at_dispatch"] += len(released)

    def _tier_marker(self) -> Optional[int]:
        """Cumulative tier-restore count before an admission (None without
        a tier) — paired with :meth:`_note_tier_restore` to stamp restores
        onto the admitted request's lane."""
        pkv = self.session.paged if self.paged else None
        if pkv is None or pkv.tier is None:
            return None
        return pkv.stats["tier_restored_pages"]

    def _note_tier_restore(self, group: Sequence[Request],
                           before: Optional[int]) -> None:
        """Per-request ``tier_restore`` instant when this admission pulled
        pages back from the host tier: the request-lane marker that lets
        ``request_timeline``/attribution see a PR 8 restore without joining
        against the ``("cache", "tier")`` lane. A multi-request group
        shares one delta (restores are per-plan inside the insert; the
        group rows ride along so a reader knows the count is shared)."""
        if before is None or not self.tracer.enabled:
            return
        delta = self.session.paged.stats["tier_restored_pages"] - before
        if delta <= 0:
            return
        for r in group:
            self.tracer.instant(
                "tier_restore", ("req", r.request_id), block=self.blocks,
                args={"pages": int(delta), "group_rows": len(group)})

    def _insert_group(self, group: List[Request], slot_ids: List[int],
                      bucket: int) -> List[int]:
        """Admit ``group`` into ``slot_ids`` by ONE insert program call, and
        decide from what the engine sees when its first tokens are fetched
        and when a slot is given back.

        Rows are decoding at the dispatch (``decoding`` below, what the
        ``admission`` span says): the tokens are fetched at once, inside the
        span; a decode block follows in this round and needs them. Nothing
        decodes (or the loop is the async one, whose blocks read the tokens
        on the device): the first tokens and the routing sums stay on the
        device, the admission is queued on ``_first_pending`` and
        :meth:`_settle_firsts` fetches it later, so the host plans and
        dispatches the next insert while this one runs. A prefill worker
        never defers: its handoff needs the token now.

        A row of a deferred insert whose budget is one token is finished
        whatever that token is: its slot is never taken, the pending entry
        carries the REQUEST, its completion is built when the token arrives,
        as of this block, and the slots of such rows are RETURNED for the
        caller (``_admit_loop``) to give back pages and table rows right
        after the dispatch (the device runs programs in order, so the next
        insert may take them). Every other row keeps its slot until it
        retires."""
        # the slots are claimed HERE: the requests' ``queued`` spans end and
        # their ``admit`` is stamped at this instant, before the prefill
        claimed = time.perf_counter()
        rows = len(group)
        # the rows this admission stalls while its program runs
        decoding = int((self._active & ~self._done).sum())
        defer = self.role != "prefill" and (self.async_loop or not decoding)
        if not self.async_loop:
            # the sync round leaves at most ONE insert unfetched behind the
            # one it dispatches (two in the device's queue), and none behind
            # an insert whose tokens it fetches itself
            self._settle_firsts(keep_newest=defer)
        span_args = None
        if self.tracer.enabled:
            span_args = {
                "rows": rows, "bucket": int(bucket), "decoding": decoding,
                "rids": [r.request_id for r in group]}
        with self.tracer.span("admission", (self.lane, "phases"),
                              block=self.blocks, args=span_args):
            ids = np.zeros((rows, bucket), np.int32)
            lens = np.zeros((rows,), np.int32)
            for i, r in enumerate(group):
                ids[i, : r.prompt.size] = r.prompt
                lens[i] = r.prompt.size
            # paged mode reserves pages for the decode room only (budget + one
            # block of post-budget overrun writes, which land in owned pages or
            # scratch — never a neighbour); the contiguous path ignores the
            # kwarg. A prefill worker reserves NOTHING beyond the prompt — its
            # first-token sample writes no KV and the decode room is allocated
            # by the adopting decode worker.
            reserve = np.asarray(
                [0 if self.role == "prefill"
                 else r.max_new_tokens + self._reserve_slack() for r in group],
                np.int64)
            aslots = (np.asarray([self._adapter_slot(r) for r in group], np.int32)
                      if self.lora else None)
            tier_before = self._tier_marker()
            # ONE program call: the prompt's KV, the rows' request keys into
            # slot_keys and their first tokens (token index 0 of each request's
            # own key stream, fold_in(req_key, 0): the derivation the chunked
            # path's final chunk and both decode modes use; constrained too, by
            # each grammar's START state), all inside it. The inputs ride the
            # call as host arrays, and nothing else runs on the device for this
            # admission (host-only simulation: the stub's token function is the
            # whole sampling path).
            self._dispatch("insert", lambda: self.lm.insert(
                self.session, np.asarray(slot_ids, np.int32), ids, lengths=lens,
                pad_token_id=self.pad_token_id,
                reserve_tokens=reserve if self.paged else None,
                adapter_slots=aslots,
                # adapter namespace for the radix walk — prefix KV reuse is
                # scoped per adapter (cross-adapter reuse = wrong tokens)
                ns=[r.adapter for r in group] if self.paged else None,
                first=self._first_inputs(group)))
            self._note_tier_restore(group, tier_before)
            self.stats["inserts"] += 1
            self.stats["inserted_requests"] += rows
            self._count_insert_program()
            self.stats["insert_fetches_deferred"] += int(defer)
            self.stats["inserts_overlapped"] += int(bool(self._first_pending))
            # a deferred insert's outputs stay on the device: fetching them
            # here would block the host on the program (and, async, on the
            # in-flight decode block it chains after: session.cache is that
            # block's donated output future) through work that needs none
            # of them
            first_dev = routing = None
            if self._sim:
                first = np.asarray(self.lm.sim_first_tokens(
                    [r.request_id for r in group], [0] * rows), np.int64)
            else:
                first_dev = self.session.first_tokens
                # the insert's routing sums (a model with experts, paged) come
                # to the host with its first tokens: here, or with the deferred
                # record
                routing = (self.session.insert_routing,
                           self.session.insert_scanned)
                first = None if defer else self._fetch_first(first_dev, routing)
        now = time.perf_counter()
        seen = {"claimed": claimed, "bucket": bucket, "rows": rows}
        released = []
        for i, (r, slot) in enumerate(zip(group, slot_ids)):
            r.start_block = self.blocks
            r.first_token_block = self.blocks
            self._trace_queued(r, claimed)
            if defer:
                # whatever its one token is, the row is finished: no slot
                leaves = r.max_new_tokens == 1
                # the RECORD (and in sim, only the record — the value is
                # host-known) waits for the settle, so a 1-token budget
                # retires on the same virtual block in sim and real mode
                self._first_pending.append({
                    "slot": slot, "rid": r.request_id, "idx": i,
                    "fut": first_dev, "block": self.blocks,
                    "insert": self.stats["inserts"], "seen": seen,
                    "routing": routing,
                    "val": None if first is None else int(first[i]),
                    "req": r if leaves else None})
                if leaves:
                    released.append(slot)
                    continue
            else:
                self._observe_first_token(r, slot, now, **seen)
            self.slots[slot] = r
            self._out[r.request_id] = []
            self._out_ts[r.request_id] = []
            self._lengths[slot] = lens[i]
            self._active[slot] = True
            self._done[slot] = False
            self._eos[slot] = -1 if r.eos_token_id is None else r.eos_token_id
            self._temp[slot] = r.temperature
            self._greedy[slot] = r.greedy
            self._gen_counts[slot] = 1
            self._adapter_idx[slot] = 0 if aslots is None else aslots[i]
            self._gidx[slot] = self._grammar_slot(r)
            self._gstate[slot] = 0
            self._gbudget[slot] = r.max_new_tokens
            if not defer:
                self._tok[slot] = int(first[i])
                self._record(slot, int(first[i]), now)
                self._advance_grammar(slot, int(first[i]))
            elif self._sim:
                self._tok[slot] = int(first[i])
            if self.async_loop:
                # the row's override at the next pipelined dispatch: the
                # host mirrors, or the token still on the device
                self._staged[slot] = (
                    {"fut": first_dev, "idx": i}
                    if defer and not self._sim else None)
        if self.role == "prefill":
            # disaggregation: the prompt's KV is done and its first token
            # sampled — hand the pages to the decode pool and free the slot
            # (streams finished AT the first token retire locally instead)
            self._handoff_group(list(slot_ids))
        return released

    # --- chunked prefill (the stall-free admission path) ------------------

    def _begin_chunked(self, req: Request, slot: int) -> None:
        """Claim ``slot`` for a chunked admission: the slot leaves the free
        pool NOW (so decode membership is stable) but stays decode-inactive;
        prefill happens across rounds in :meth:`_advance_prefill`."""
        claimed = time.perf_counter()
        chunk = None
        written = 0
        if self.paged:
            tier_before = self._tier_marker()
            reserve = (0 if self.role == "prefill"
                       else req.max_new_tokens + self._reserve_slack())
            chunk = self.session.paged.begin_chunked(
                req.prompt.tolist(), req.prompt.size + reserve,
                ns=req.adapter)
            written = chunk.start           # prefix hit: skip reused pages
            self._note_tier_restore([req], tier_before)
        req.start_block = self.blocks
        self._trace_queued(req, claimed)
        if self.tracer.enabled:
            self.tracer.instant(
                "chunk_begin", ("req", req.request_id), block=self.blocks,
                args={"slot": int(slot), "prompt_len": int(req.prompt.size),
                      "prefix_reused_tokens": int(written)})
        self.slots[slot] = req
        self._active[slot] = False
        self._done[slot] = False
        if not self._sim:
            self._slot_keys = self._slot_keys.at[slot].set(
                self._req_key(req.request_id))
        # chunk prefill must already run under the request's adapter — the
        # KV it writes is adapter-specific
        self._adapter_idx[slot] = self._adapter_slot(req)
        self._prefilling[slot] = _PrefillInFlight(
            req=req, slot=slot, written=written, chunk=chunk)
        self._prefill_q.append(slot)

    def _advance_prefill(self) -> None:
        """Spend this round's prefill budget: up to ``prefill_chunk_tokens``
        prompt tokens across the in-flight admissions in FIFO order (a
        finishing request's tail leaves budget for the next). Pool pressure
        mid-chunk (paged) rolls the WHOLE admission back atomically and
        requeues it at the queue head."""
        budget = self.prefill_chunk_tokens
        while budget > 0 and self._prefill_q:
            slot = self._prefill_q[0]
            st = self._prefilling[slot]
            req = st.req
            remaining = req.prompt.size - st.written
            n = min(budget, remaining)
            final = n == remaining
            tables = None
            if self.paged:
                pkv = self.session.paged
                try:
                    pkv.extend_chunked(st.chunk, st.written + n, final=final)
                except PagePoolExhausted:
                    self._abort_prefill(slot, requeue=True)
                    self.stats["deferred_admissions"] += 1
                    self._note_pool_pressure(())
                    return
                tables = pkv.chunk_table(slot, st.chunk)[None]
            ids = req.prompt[st.written: st.written + n][None]
            aslots = (np.asarray([self._adapter_idx[slot]], np.int32)
                      if self.lora else None)
            logits = self._dispatch("extend", lambda: self.lm.extend(
                self.session, np.asarray([slot], np.int32), ids,
                np.asarray([n], np.int32), np.asarray([st.written], np.int32),
                tables=tables, adapter_slots=aslots,
                first=self._first_inputs([req])))
            self.stats["chunk_program_calls"] += 1
            self._count_insert_program()
            self.stats["prefill_chunk_tokens_done"] += n
            st.written += n
            budget -= n
            if self.tracer.enabled:
                self.tracer.instant(
                    "prefill_chunk", ("req", req.request_id),
                    block=self.blocks,
                    args={"tokens": int(n), "written": int(st.written),
                          "of": int(req.prompt.size), "final": bool(final)})
            if final:
                self._finish_prefill(slot, st, logits)

    def _finish_prefill(self, slot: int, st: _PrefillInFlight,
                        logits: jax.Array) -> None:
        """Final chunk landed: commit pages (paged), sample the request's
        FIRST token from the last real chunk position (token index 0 of its
        key stream — bit-identical to what a one-shot insert would have
        sampled) and hand the slot to the decode pool."""
        req = st.req
        assert self._prefill_q[0] == slot
        self._prefill_q.popleft()
        del self._prefilling[slot]
        if self.paged:
            self.session.paged.finish_chunked(slot, st.chunk)
        self.stats["inserts"] += 1
        self.stats["inserted_requests"] += 1
        temps = np.asarray([req.temperature], np.float32)
        greedy = np.asarray([req.greedy], bool)
        defer = self.async_loop and self.role != "prefill"
        first_dev = None
        if self._sim:
            first = self.lm.sim_token(req.request_id, 0)
        else:
            key = self._req_key(req.request_id)
            sub = jax.vmap(jax.random.fold_in)(key[None],
                                               jnp.zeros((1,), jnp.int32))
            logits = self._mask_logits(
                logits, self._grammar_allowed_rows([req], [0], [0]))
            # async: same deferral as _insert_group — the sampler output
            # chains after the in-flight decode block, so fetching it here
            # would stall the pipeline
            first_dev = self.slot_sampler(
                logits, sub, jnp.asarray(temps), jnp.asarray(greedy))
            first = None
            if not defer:
                first = int(np.asarray(first_dev)[0])
                self.stats["insert_host_fetches"] += 1
        req.first_token_block = self.blocks
        if not defer:
            self._observe_first_token(req, slot, time.perf_counter(),
                                      chunked=True)
        self._out[req.request_id] = []
        self._out_ts[req.request_id] = []
        self._lengths[slot] = req.prompt.size
        self.session.active[slot] = True
        self._active[slot] = True
        self._done[slot] = False
        self._eos[slot] = -1 if req.eos_token_id is None else req.eos_token_id
        self._temp[slot] = temps[0]
        self._greedy[slot] = greedy[0]
        self._gen_counts[slot] = 1
        self._gidx[slot] = self._grammar_slot(req)
        self._gstate[slot] = 0
        self._gbudget[slot] = req.max_new_tokens
        if defer:
            self._first_pending.append({
                "slot": slot, "rid": req.request_id, "idx": 0,
                "fut": first_dev, "block": self.blocks,
                "insert": self.stats["inserts"], "seen": {"chunked": True},
                "val": first if self._sim else None, "req": None})
            if self._sim:
                self._tok[slot] = first
                self._staged[slot] = None
            else:
                self._staged[slot] = {"fut": first_dev, "idx": 0}
        else:
            self._tok[slot] = first
            self._record(slot, first, time.perf_counter())
            self._advance_grammar(slot, first)
            if self.async_loop:
                self._staged[slot] = None
        if self.role == "prefill":
            self._handoff_group([slot])

    def _abort_prefill(self, slot: int, requeue: bool) -> None:
        """Atomically unwind an in-flight chunked admission: pages released,
        the slot's DEVICE table reset to scratch (residual decode-block
        garbage writes must not land in pages the pool re-issues), slot
        freed. ``requeue`` puts the request back at the queue head — the
        whole prefill restarts later (chunk work done so far is discarded;
        correctness never depends on it)."""
        st = self._prefilling.pop(slot)
        self._prefill_q.remove(slot)
        if st.chunk is not None:
            pkv = self.session.paged
            pkv.abort_chunked(slot, st.chunk)
            if self.session.cache is not None:
                self.session.cache = _set_block_tables(self.session.cache,
                                                       pkv.tables)
        self.slots[slot] = None
        self._active[slot] = False
        self._adapter_idx[slot] = 0
        self.session.lengths[slot] = 0
        self.session.active[slot] = False
        self._staged.pop(slot, None)
        self.stats["prefill_aborts"] += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "prefill_abort", ("req", st.req.request_id),
                block=self.blocks,
                args={"requeue": bool(requeue), "written": int(st.written)})
        if requeue:
            st.req.start_block = None
            self.queue.appendleft(st.req)

    # --- recovery: replay re-prefill, corruption handling, snapshots -----
    # A request's stream is a pure function of (prompt, params, base key,
    # request id): token t draws from fold_in(fold_in(base, r), t). So ANY
    # request whose KV is lost — process restart, corrupted page — can be
    # re-prefilled from its host-side (prompt, generated) record and resume
    # bit-identical at token index len(generated). That one invariant is the
    # whole recovery story; everything below is bookkeeping around it.

    def _drain_replays(self) -> None:
        """Re-admit recovery work (restored / corruption-hit requests) into
        free slots, ahead of fresh admissions — they represent streams the
        client is already consuming. Pool pressure defers to the next block
        (retirements return pages), same as normal admission."""
        while self._replay_q:
            free = self._free_slots()
            if not free:
                return
            req, pregen, ts = self._replay_q[0]
            try:
                self._replay_admission(req, pregen, ts, free[0])
            except PagePoolExhausted:
                self.stats["deferred_admissions"] += 1
                self._note_pool_pressure(())
                return
            except (AdapterPoolExhausted, GrammarPoolExhausted):
                # a replay is a stream the client is already consuming: it
                # is never shed — it waits for a pin to return, exactly
                # like pool pressure defers to the next block
                self.stats["deferred_admissions"] += 1
                return
            except AdapterLoadError:
                self.stats["adapter_load_retries"] += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "adapter_defer", ("req", req.request_id),
                        block=self.blocks,
                        args={"adapter": req.adapter, "state": "replay"})
                return
            except GrammarLoadError:
                self.stats["grammar_load_retries"] += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "grammar_defer", ("req", req.request_id),
                        block=self.blocks,
                        args={"grammar": req.grammar, "state": "replay"})
                return
            self._replay_q.popleft()
            self._replay_tokens -= req.max_new_tokens

    def _replay_admission(self, req: Request, pregen: List[int],
                          ts: List[float], slot: int) -> None:
        """Rebuild a request's KV from scratch and resume its stream at
        token index ``len(pregen)``: prefill prompt+generated through
        largest-bucket ``extend`` chunks (prefix-cache hits skip shared
        pages where they survive), then sample token ``g`` under
        ``fold_in(req_key, g)`` — bit-identical to the uninterrupted run."""
        # a replay is recovery work, not the steady-state path — it samples
        # its resumed token synchronously, so drain what is in flight first:
        # the async pipeline (designated sync point; the next dispatch
        # restarts cold from the host mirrors, which this admission is about
        # to set) and, in either loop, the inserts whose replies are pending
        self._flush()
        aslot = 0
        if self.lora and req.adapter is not None:
            # re-pin the stream's adapter BEFORE any page work (it may have
            # been evicted while the request sat in the replay queue);
            # exhaustion/load faults propagate to _drain_replays, which
            # defers the replay to a later block — never a wrong adapter
            if req.request_id not in self._adapter_pins:
                self.session.adapters.acquire(req.adapter)
                self._adapter_pins[req.request_id] = req.adapter
            aslot = self.session.adapters.slot_of(req.adapter)
        gslot = 0
        if self.grammar and req.grammar is not None:
            # re-pin the grammar tables before any page work (same
            # discipline as the adapter pin above); exhaustion/load faults
            # propagate to _drain_replays, which defers the replay
            if req.request_id not in self._grammar_pins:
                self.session.grammars.acquire(req.grammar)
                self._grammar_pins[req.request_id] = req.grammar
            gslot = self.session.grammars.slot_of(req.grammar)
        g = len(pregen)
        seq = (np.concatenate([req.prompt, np.asarray(pregen, np.int32)])
               if g else np.asarray(req.prompt, np.int32))
        total = int(seq.size)
        chunk_cap = self.lm.buckets[-1]
        st = None
        written = 0
        pkv = self.session.paged if self.paged else None
        if pkv is not None:
            tier_before = self._tier_marker()
            st = pkv.begin_chunked(
                seq.tolist(),
                total + (req.max_new_tokens - g) + self._reserve_slack(),
                ns=req.adapter)
            written = st.start
            self._note_tier_restore([req], tier_before)
        logits = None
        try:
            while written < total:
                n = min(chunk_cap, total - written)
                final = written + n == total
                tables = None
                if pkv is not None:
                    pkv.extend_chunked(st, written + n, final=final)
                    tables = pkv.chunk_table(slot, st)[None]
                ids = seq[written: written + n][None]
                w = written
                logits = self._dispatch("extend", lambda: self.lm.extend(
                    self.session, np.asarray([slot], np.int32), ids,
                    np.asarray([n], np.int32), np.asarray([w], np.int32),
                    tables=tables,
                    adapter_slots=(np.asarray([aslot], np.int32)
                                   if self.lora else None),
                    first=self._first_inputs([req])))
                self._count_insert_program()
                written += n
        except BaseException:
            # atomic unwind: every page hold released, device table reset —
            # the request stays in the replay queue for the next attempt
            if pkv is not None:
                pkv.abort_chunked(slot, st)
                if self.session.cache is not None:
                    self.session.cache = _set_block_tables(
                        self.session.cache, pkv.tables)
            self.session.lengths[slot] = 0
            self.session.active[slot] = False
            raise
        if pkv is not None:
            pkv.finish_chunked(slot, st)
        temps = np.asarray([req.temperature], np.float32)
        greedy = np.asarray([req.greedy], bool)
        rstate = 0
        if self._sim:
            key = None
            tok = self.lm.sim_token(req.request_id, g)
        else:
            key = self._req_key(req.request_id)
            sub = jax.vmap(jax.random.fold_in)(key[None],
                                               jnp.full((1,), g, jnp.int32))
            # resumed constrained stream: the DFA state is a pure function
            # of the delivered tokens — walk them, then mask token g
            # exactly as the uninterrupted run would have (snapshot/
            # failover carries the grammar NAME; the state is recomputed,
            # so it cannot drift)
            rstate = (self._grammar_walk(req.grammar, 0, pregen)
                      if self.grammar and req.grammar is not None else 0)
            logits = self._mask_logits(
                logits, self._grammar_allowed_rows([req], [rstate], [g]))
            tok = int(np.asarray(self.slot_sampler(
                logits, sub, jnp.asarray(temps), jnp.asarray(greedy)))[0])
            self.stats["insert_host_fetches"] += 1
        now = time.perf_counter()
        if req.start_block is None:
            req.start_block = self.blocks
        if req.first_token_block is None:
            req.first_token_block = self.blocks
        self.slots[slot] = req
        self._out[req.request_id] = [int(t) for t in pregen]
        self._out_ts[req.request_id] = list(ts[:g])
        self._lengths[slot] = total
        self.session.active[slot] = True
        self._active[slot] = True
        self._done[slot] = False
        self._eos[slot] = -1 if req.eos_token_id is None else req.eos_token_id
        self._temp[slot] = temps[0]
        self._greedy[slot] = greedy[0]
        self._tok[slot] = tok
        if not self._sim:
            self._slot_keys = self._slot_keys.at[slot].set(key)
        self._gen_counts[slot] = g + 1
        self._adapter_idx[slot] = aslot
        self._gidx[slot] = gslot
        self._gstate[slot] = rstate
        self._gbudget[slot] = req.max_new_tokens
        if g == 0:
            self._observe_first_token(req, slot, now, replayed=True)
        elif self.tracer.enabled:
            # same stamp as the resumed token below: a time-sorted timeline
            # must show replay_admit BEFORE the token it resumed
            self.tracer.instant(
                "replay_admit", ("req", req.request_id), block=self.blocks,
                ts=now, args={"slot": int(slot), "resumed_at": int(g)})
        self._record(slot, tok, now)
        self._advance_grammar(slot, tok)
        if self.async_loop:
            self._staged[slot] = None
        self.stats["inserts"] += 1
        self.stats["inserted_requests"] += 1

    def _page_dtype(self) -> str:
        """This engine's resolved page-pool storage dtype as a string —
        the handoff framing stamp ("int8" pools, else the config compute
        dtype the pool leaves are allocated in)."""
        cfg = self.lm.config
        pd = getattr(cfg, "page_dtype", None)
        if pd == "int8":
            return "int8"
        # sim-mode configs (inference/simlm.py) carry no compute dtype
        return str(jnp.dtype(pd or getattr(cfg, "dtype", None) or jnp.float32))

    def _read_page_bytes(self, page: int) -> Dict[str, np.ndarray]:
        """Host copy of one physical page's K/V bytes across every layer —
        the tier's spill read ({cache-leaf path: (L, page_size, kv, hd)
        array}). Runs between blocks only; device programs never see it."""
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.session.cache)[0]:
            p = jax.tree_util.keystr(path)
            if p.endswith(_KV_PAGE_LEAVES):
                out[p] = np.asarray(leaf[:, int(page)])
        return out

    def _write_page_bytes(self, page: int,
                          data: Dict[str, np.ndarray]) -> None:
        """Write host bytes back into physical page ``page`` of every K/V
        pool leaf — the tier's restore/repair write (the functional update
        replaces the session cache between blocks, same discipline as
        ``_set_block_tables``)."""
        def fix(path, leaf):
            got = _page_payload(data, jax.tree_util.keystr(path))
            if got is not None:
                return leaf.at[:, int(page)].set(jnp.asarray(got, leaf.dtype))
            return leaf

        from neuronx_distributed_tpu.inference.partition import repin

        # host-side eager scatters on tp-sharded pool leaves may decommit
        # the serving layout — re-pin so the AOT programs keep accepting
        # the cache (partition.repin is a no-op when nothing drifted)
        self.session.cache = repin(jax.tree_util.tree_map_with_path(
            fix, self.session.cache), self.session.cache)

    def _io_pad(self, pages: List[int]) -> List[int]:
        """Pad a page-id list to the slot's full page count by REPEATING
        the last id: the batched gather/scatter then compiles exactly ONE
        program shape per leaf — a variable-length handoff would compile a
        new program per distinct prompt size, and that compile would land
        mid-run as a decode-clock spike. A duplicate index in a scatter
        rewrites the same page with the same bytes — safe; in a gather it
        fetches redundant rows the caller slices off."""
        n = self.session.paged.pages_per_slot
        return list(pages) + [pages[-1]] * (n - len(pages))

    def _read_pages_bytes(self, pages: List[int]) -> List[Dict[str, np.ndarray]]:
        """Batched :meth:`_read_page_bytes`: ONE gather + fetch per K/V
        leaf for the whole page list, split back into the per-page dicts
        the handoff's per-page crc framing wants — a 16-page handoff costs
        2 host ops per leaf instead of 16."""
        idx = jnp.asarray(self._io_pad(pages), jnp.int32)
        out: List[Dict[str, np.ndarray]] = [{} for _ in pages]
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.session.cache)[0]:
            p = jax.tree_util.keystr(path)
            if p.endswith(_KV_PAGE_LEAVES):
                arr = np.asarray(leaf[:, idx])       # (L, n_pad, page, kv, hd)
                for i in range(len(pages)):
                    out[i][p] = arr[:, i]
        return out

    def _write_pages_bytes(self, pages: List[int],
                           datas: List[Dict[str, np.ndarray]]) -> None:
        """Batched :meth:`_write_page_bytes`: one functional update per
        K/V leaf for the whole page list — the adoption path's device
        write (a per-page ``at[].set`` would copy the whole pool once PER
        PAGE; this copies it once per leaf)."""
        idx = jnp.asarray(self._io_pad(pages), jnp.int32)
        pad = len(idx) - len(pages)

        def fix(path, leaf):
            p = jax.tree_util.keystr(path)
            rows = [_page_payload(d, p) for d in datas]
            if rows[0] is None:
                return leaf
            return leaf.at[:, idx].set(jnp.stack(
                [jnp.asarray(r, leaf.dtype) for r in rows + rows[-1:] * pad],
                axis=1))

        from neuronx_distributed_tpu.inference.partition import repin

        self.session.cache = repin(jax.tree_util.tree_map_with_path(
            fix, self.session.cache), self.session.cache)

    def _corrupt_page_bytes(self, pages: List[int]) -> None:
        """Physically garble the K/V pool bytes of ``pages`` in every layer.
        The injected fault is REAL — the recovery replay is thereby proven
        to rewrite the data, not merely re-point block tables."""
        def fix(path, leaf):
            p = jax.tree_util.keystr(path)
            if p.endswith(_KV_PAGE_LEAVES):
                for pg in pages:
                    # astype (not dtype=) so int8 pools garble by wrap
                    # instead of raising on the unsafe cast
                    leaf = leaf.at[:, pg].set(
                        jnp.asarray(104729.0).astype(leaf.dtype))
            return leaf

        from neuronx_distributed_tpu.inference.partition import repin

        self.session.cache = repin(jax.tree_util.tree_map_with_path(
            fix, self.session.cache), self.session.cache)

    def inject_page_corruption(self, pages: List[int]) -> None:
        """Public corruption seam (ops drills / tests): declare ``pages``
        corrupted between blocks — the engine garbles their bytes and runs
        the full detect/invalidate/replay recovery."""
        if not self.paged:
            raise ValueError("page corruption applies to paged engines only")
        if getattr(self.lm, "slot_rows", ()):
            raise ValueError(
                "page corruption recovery replays pages; this model keeps "
                f"per-slot state {self.lm.slot_rows} beside them")
        self._handle_corrupt_pages([int(p) for p in pages])
        self.stats.setdefault("injected_corruptions", 0)
        self.stats["injected_corruptions"] += len(pages)

    def _handle_corrupt_pages(self, pages: List[int]) -> None:
        """Corrupted-page recovery, in dependency order: garble the bytes
        (make the fault real), REPAIR in place from the host tier where an
        inclusive checksum-verified copy exists (the subtree stays valid,
        no stream replays — restore beats re-prefill), invalidate the
        remaining pages from the prefix index (no future sharer may splice
        them in), unwind any mid-prefill admission holding one (it restarts
        from the queue), then re-prefill every decoding request reading
        through one — their streams resume bit-identical (per-request
        rng)."""
        pkv = self.session.paged
        # recovery reads _out (delivered-so-far) to rebuild replay
        # records — drain the async pipeline and the pending replies first
        # so those records are whole, then retire streams the drain
        # completed: a finished stream's KV needs no repair, and replaying
        # it would sample one token past its budget (_replay_admission
        # resumes at len(pregen))
        self._flush()
        self._retire_finished()
        bad = {int(p) for p in pages}
        all_bad = sorted(bad)
        replays_before = self.stats["corrupt_page_replays"]
        repairs_before = self.stats["tier_page_repairs"]
        if self.tracer.enabled:
            self.tracer.instant(
                "fault:corrupt_pages", (self.lane, "faults"),
                block=self.blocks,
                args={"pages": sorted(bad)})
        self._corrupt_page_bytes(sorted(bad))
        if pkv.tier is not None:
            repaired = {p for p in sorted(bad)
                        if pkv.repair_page_from_tier(p)}
            if repaired:
                self.stats["tier_page_repairs"] += len(repaired)
                bad -= repaired
            if not bad:
                self._incident_corruption(all_bad, replays_before,
                                          repairs_before)
                return
        if pkv.prefix is not None:
            pkv.prefix.invalidate_pages(sorted(bad))
        for slot, st in list(self._prefilling.items()):
            held = set(st.chunk.shared + st.chunk.owned) if st.chunk else set()
            if bad & held:
                self._abort_prefill(slot, requeue=True)
        for slot in range(self.lm.max_batch):
            req = self.slots[slot]
            if (req is None or slot in self._prefilling
                    or not bad & set(pkv.slot_pages(slot))):
                continue
            pregen = list(self._out.get(req.request_id, []))
            ts = list(self._out_ts.get(req.request_id, []))
            self.lm.retire(self.session, np.asarray([slot], np.int32))
            self.slots[slot] = None
            self._active[slot] = False
            self._done[slot] = False
            self._adapter_idx[slot] = 0   # the pin survives for the replay
            self._replay_q.append((req, pregen, ts))
            self._replay_tokens += req.max_new_tokens
            self.stats["corrupt_page_replays"] += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "corrupt_replay", ("req", req.request_id),
                    block=self.blocks,
                    args={"delivered": len(pregen)})
        self._incident_corruption(all_bad, replays_before, repairs_before)
        self._drain_replays()

    def _incident_corruption(self, pages: List[int], replays_before: int,
                             repairs_before: int) -> None:
        """Flight-recorder dump for one corruption episode: the poisoned
        pages, how many were repaired in place from the tier vs replayed,
        and the engine state at detection time."""
        if self.incident is None:
            return
        self.incident.trigger(
            "page_corruption", self.blocks,
            details={
                "pages": pages,
                "replays": self.stats["corrupt_page_replays"] - replays_before,
                "tier_repairs": self.stats["tier_page_repairs"]
                - repairs_before,
            },
            state=self.state_summary(),
            slo=self.slo_status())

    # --- prefill/decode disaggregation: KV-page handoff ------------------
    # A prefill worker's product is (first token, prompt KV pages); a
    # decode worker's admission path is page ADOPTION. Both ends move bytes
    # through the PR 8 page-IO closures (_read_page_bytes/_write_page_bytes)
    # with HostPageTier's crc32 framing, so a corrupted transfer is caught
    # by checksum and degrades to a local re-prefill — never a wrong token
    # (the per-request rng contract again). See inference/disagg.py for the
    # router-side choreography.

    def _handoff_group(self, slot_ids: List[int]) -> None:
        """Package each freshly-prefilled slot's prompt pages into a sealed
        :class:`~neuronx_distributed_tpu.inference.disagg.KVHandoff` on
        ``self.outbox`` and release the slot (pages read out BEFORE retire
        frees them). Slots already done — the budget was 1 token, or EOS
        landed on the first sample — keep their state and retire locally
        with a normal completion: there is nothing left to decode."""
        from neuronx_distributed_tpu.inference.disagg import KVHandoff
        from neuronx_distributed_tpu.inference.partition import tp_degree

        pkv = self.session.paged
        ps = pkv.page_size
        tp = tp_degree()
        for slot in slot_ids:
            req = self.slots[slot]
            if req is None or self._done[slot]:
                continue
            rid = req.request_id
            n_copy = -(-req.prompt.size // ps)
            pages = [int(p) for p in pkv.tables[slot][:n_copy]]
            payloads = self._read_pages_bytes(pages)
            first = int(self._out[rid][0])
            ts_list = self._out_ts.get(rid) or [time.perf_counter()]
            h = KVHandoff(req=req, first_token=first,
                          first_ts=float(ts_list[0]), page_size=ps,
                          payloads=payloads, tp_degree=tp,
                          page_dtype=self._page_dtype())
            h.seal()
            self.outbox.append(h)
            self.stats["handoffs_sent"] += 1
            if self.tracer.enabled:
                now = time.perf_counter()
                self.tracer.instant(
                    "migrate_send", ("req", rid), block=self.blocks, ts=now,
                    args={"pages": n_copy,
                          "prompt_len": int(req.prompt.size)})
                self.tracer.instant(
                    "migrate:send", (self.lane, "migrate"),
                    block=self.blocks,
                    args={"rid": rid, "pages": n_copy})
            # the stream now lives in the handoff: free the slot (prompt
            # pages registered in the prefix index stay resident, so this
            # worker's radix keeps the prefix hot for future admissions)
            self.lm.retire(self.session, np.asarray([slot], np.int32))
            self.slots[slot] = None
            self._active[slot] = False
            self._done[slot] = False
            # the pin moves with the stream: released here, re-taken by the
            # adopting decode worker (the drain-migration discipline).
            # Adapter pins CANNOT exist on this seam — disagg submit
            # rejects adapter-labeled requests (adopted KV is
            # adapter-specific); the assert is the static witness
            # nxdcheck's resource-pairing rule checks, and it fires in
            # tests if that restriction is ever relaxed without teaching
            # the handoff to migrate the pin
            assert req.request_id not in self._adapter_pins
            self._release_grammar(req)
            self._gidx[slot] = 0
            self._out.pop(rid, None)
            self._out_ts.pop(rid, None)
            self._last_tok_ts.pop(rid, None)
            self._submit_ts.pop(rid, None)

    def adopt_handoff(self, h) -> str:
        """Adopt one migrated stream (decode role): verify the handoff's
        per-page checksums, allocate the slot's full footprint through
        :meth:`PagedKVCache.adopt_pages`, write the prompt KV bytes into
        fresh device pages, and enter the stream into the decode pool at
        token index 1 (its first token was sampled on the prefill side).

        Returns the adoption verdict: ``"adopted"`` (stream live),
        ``"deferred"`` (no free slot / pool pressure — retry next block, as
        retirements return pages), or ``"degraded"`` (checksum failure: the
        handoff bytes are poison; the caller re-prefills the stream locally
        via :meth:`resume` — bit-identical, per the rng contract)."""
        if self.role != "decode":
            raise ValueError("adopt_handoff requires role='decode'")
        req = h.req
        free = self._free_slots()
        if not free:
            return "deferred"
        if not self._pool_can_admit(req.prompt.size, req.max_new_tokens):
            self._note_pool_pressure([req])
            return "deferred"
        from neuronx_distributed_tpu.inference.partition import tp_degree
        my_tp = tp_degree()
        if getattr(h, "tp_degree", 1) != my_tp:
            # structured cross-degree rejection: the framing was sealed
            # under a different TP degree, and an adopter has no way to
            # validate foreign-degree framing assumptions — degrade to a
            # local re-prefill (bit-identical per the rng contract)
            # instead of corrupting the pool silently
            if self.tracer.enabled:
                self.tracer.instant(
                    "migrate:tp_mismatch", (self.lane, "migrate"),
                    block=self.blocks,
                    args={"rid": req.request_id,
                          "src_tp": int(getattr(h, "tp_degree", 1)),
                          "dst_tp": int(my_tp)})
            return "degraded"
        my_pd = self._page_dtype()
        if getattr(h, "page_dtype", "float32") != my_pd:
            # foreign page dtype: the payload bytes are in a storage
            # format this pool cannot hold (and re-quantizing mid-stream
            # would fork the numerics) — degrade to local re-prefill,
            # exactly the tp_degree-mismatch discipline
            if self.tracer.enabled:
                self.tracer.instant(
                    "migrate:page_dtype_mismatch", (self.lane, "migrate"),
                    block=self.blocks,
                    args={"rid": req.request_id,
                          "src_dtype": str(getattr(h, "page_dtype",
                                                   "float32")),
                          "dst_dtype": my_pd})
            return "degraded"
        if not h.verify():
            if self.tracer.enabled:
                self.tracer.instant(
                    "migrate:corrupt", (self.lane, "migrate"),
                    block=self.blocks, args={"rid": req.request_id})
            return "degraded"
        gslot = 0
        if self.grammar and req.grammar is not None:
            # pin the stream's grammar tables before any page work; pool
            # pressure defers the adoption (the handoff survives at the
            # router), a load fault retries next block — never a stream
            # decoded without its mask
            try:
                if req.request_id not in self._grammar_pins:
                    self.session.grammars.acquire(req.grammar)
                    self._grammar_pins[req.request_id] = req.grammar
            except (GrammarPoolExhausted, GrammarLoadError):
                self.stats["deferred_admissions"] += 1
                return "deferred"
            gslot = self.session.grammars.slot_of(req.grammar)
        slot = free[0]
        pkv = self.session.paged
        t0 = time.perf_counter()
        try:
            pages = pkv.adopt_pages(
                slot, req.prompt.tolist(), h.payloads,
                self._write_pages_bytes,
                req.prompt.size + req.max_new_tokens + self._reserve_slack())
        except PagePoolExhausted:
            self.stats["deferred_admissions"] += 1
            self._note_pool_pressure([req])
            return "deferred"
        # install the device-side slot state between blocks: the block
        # table rows (host-authoritative) and THIS slot's cache_index only
        self.session.cache = _set_block_tables(self.session.cache,
                                               pkv.tables)
        self.session.cache = _set_cache_index_rows(
            self.session.cache, [slot], [req.prompt.size])
        rid = req.request_id
        self._next_id = max(self._next_id, rid + 1)
        self.slots[slot] = req
        self._out[rid] = [int(h.first_token)]
        self._out_ts[rid] = [h.first_ts]
        self._last_tok_ts[rid] = h.first_ts
        self._lengths[slot] = req.prompt.size
        self.session.lengths[slot] = req.prompt.size
        self.session.active[slot] = True
        self._active[slot] = True
        self._done[slot] = False
        self._eos[slot] = -1 if req.eos_token_id is None else req.eos_token_id
        self._temp[slot] = req.temperature
        self._greedy[slot] = req.greedy
        self._tok[slot] = int(h.first_token)
        self._slot_keys = self._slot_keys.at[slot].set(self._req_key(rid))
        self._gen_counts[slot] = 1
        self._adapter_idx[slot] = 0
        self._gidx[slot] = gslot
        # the DFA already consumed the prefill-side first token
        self._gstate[slot] = (
            self._grammar_walk(req.grammar, 0, [int(h.first_token)])
            if gslot else 0)
        self._gbudget[slot] = req.max_new_tokens
        # async: the adopted row enters the NEXT dispatch via the host
        # mirrors set above (its first token is host-known — no deferral);
        # the functional cache updates chain after any in-flight block
        # automatically, and that block's inputs captured the old tables
        if self.async_loop:
            self._staged[slot] = None
        self.stats["handoffs_adopted"] += 1
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._m_handoff.observe(dt_ms)
        if self.tracer.enabled:
            now = time.perf_counter()
            self.tracer.instant(
                "migrate_adopt", ("req", rid), block=self.blocks, ts=now,
                args={"slot": int(slot), "pages": len(h.payloads),
                      "ms": round(dt_ms, 3)})
            self.tracer.instant(
                "migrate:recv", (self.lane, "migrate"), block=self.blocks,
                args={"rid": rid, "pages": len(h.payloads),
                      "total_pages": len(pages)})
        return "adopted"

    # --- router hooks: resume, drain extraction --------------------------
    # The Router's failover/drain machinery moves whole requests between
    # replicas. Nothing here invents new recovery mechanics — it re-exposes
    # the replay/abort primitives the snapshot and corruption paths already
    # use, as public seams.

    def resume(self, req: Request, generated: Sequence[int] = ()) -> int:
        """Enqueue a recovery replay of ``req``: its KV is rebuilt from
        (prompt + ``generated``) at the next block boundary and the stream
        resumes at token index ``len(generated)`` — bit-identical to an
        uninterrupted run, per the per-request rng contract. The Router's
        failover path (replica died mid-stream) and any external recovery
        record land here."""
        if self.role == "prefill":
            raise ValueError(
                "a prefill worker cannot resume decode streams — route "
                "replays to a decode worker (DisaggRouter does)")
        self._next_id = max(self._next_id, req.request_id + 1)
        req.start_block = None
        req.first_token_block = None
        self._replay_q.append((req, [int(t) for t in generated], []))
        self._replay_tokens += req.max_new_tokens
        return req.request_id

    # --- conversation tier: park / resume --------------------------------
    # The durable third rung of the capacity ladder (ROADMAP #21): an idle
    # decoding stream's KV pages + request state spill to the park store
    # (inference/conversation_tier.py) and the slot is evicted ENTIRELY —
    # 0 device pages, 0 host-tier pages, 0 prefix-index entries. Resume
    # re-adopts the pages without re-prefill (the adopt_handoff discipline:
    # verify framing stamps, pin adapter/grammar BEFORE page work, install
    # mirrors between blocks); any degradation — torn manifest, corrupt
    # bytes, read fault, foreign tp_degree/page_dtype, state-only park —
    # lands on the replay path, bit-identical to a cold stream.

    def _parked_request(self, st: dict, delta: int) -> Request:
        """Rebuild a :class:`Request` from a parked state dict, shifting
        every block stamp by ``delta`` (blocks spent parked are off the
        clock: a user's think-time must not burn stream deadlines or count
        as decode/queue time in the completion)."""
        def shift(v):
            return None if v is None else int(v) + delta

        req = Request(
            request_id=int(st["request_id"]),
            prompt=np.asarray(st["prompt"], np.int32),
            max_new_tokens=int(st["max_new_tokens"]),
            eos_token_id=st.get("eos_token_id"),
            temperature=float(st.get("temperature", 0.0)),
            greedy=bool(st.get("greedy", True)),
            arrival_block=int(st.get("arrival_block", 0)) + delta,
            submit_block=self.blocks,
            ttft_deadline_block=shift(st.get("ttft_deadline_block")),
            deadline_block=shift(st.get("deadline_block")),
            tenant=st.get("tenant", "default"),
            adapter=st.get("adapter"),
            grammar=st.get("grammar"),
        )
        req.start_block = shift(st.get("start_block"))
        req.first_token_block = shift(st.get("first_token_block"))
        return req

    def park(self, request_id: int) -> str:
        """Park one decoding conversation to the durable tier and evict it
        from device AND host. Returns ``"parked"`` (the injected write
        faults — state-only or torn park — are deliberately invisible
        here: they surface at resume, as degradations) or ``"retired"``
        when the async drain finds the stream already finished.

        Ordering is crash-consistent: pages are exported and the store
        write completes BEFORE any engine state mutates — a storage
        exception (after ``_retry`` exhaustion) propagates with the
        conversation still live, nothing leaked, nothing lost. Only after
        the durable write does the eviction commit; from there every exit
        releases the slot, its pages, and its adapter/grammar pins."""
        if self.park_store is None:
            raise ValueError(
                "parking requires park_dir/park_store at construction")
        if self.role == "prefill":
            raise ValueError(
                "prefill workers hold no decode streams to park")
        rid = int(request_id)
        slot = next((i for i, r in enumerate(self.slots)
                     if r is not None and r.request_id == rid), None)
        if slot is None or slot in self._prefilling:
            raise ValueError(f"request {rid} is not a decoding stream")
        # designated sync point: the in-flight block may still emit for (or
        # finish) this slot, and a pending reply is delivered before a
        # stream is frozen — drain both
        self._flush()
        self._retire_finished()
        cur = self.slots[slot]
        if cur is None or cur.request_id != rid:
            return "retired"
        if self._done[slot]:
            # finished while we looked: nothing to park, the next
            # scheduling pass retires it with a normal completion
            return "retired"
        from neuronx_distributed_tpu.inference.partition import tp_degree

        req = self.slots[slot]
        t0 = time.perf_counter()
        generated = [int(t) for t in self._out[rid]]
        length = int(self._lengths[slot])
        # the stream-state invariant: the cache covers prompt +
        # generated[:-1] (the last sampled token rides _tok, unfed), so
        # length == prompt + len(generated) - 1 and the page export copies
        # exactly ceil(length/page_size) pages
        covered = [int(t) for t in req.prompt] + generated[:-1]
        assert length == len(covered), (length, len(covered))
        pkv = self.session.paged
        n_copy = -(-length // pkv.page_size)
        pages = [int(p) for p in pkv.tables[slot][:n_copy]]
        payloads = self._read_pages_bytes(pages)
        state = {
            "request_id": rid,
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": (None if req.eos_token_id is None
                             else int(req.eos_token_id)),
            "temperature": float(req.temperature),
            "greedy": bool(req.greedy),
            "arrival_block": int(req.arrival_block),
            "ttft_deadline_block": req.ttft_deadline_block,
            "deadline_block": req.deadline_block,
            "tenant": req.tenant,
            "adapter": req.adapter,
            "grammar": req.grammar,
            "grammar_state": (int(self._gstate[slot])
                              if self.grammar and req.grammar is not None
                              else None),
            "generated": generated,
            "length": length,
            "parked_block": int(self.blocks),
            "start_block": req.start_block,
            "first_token_block": req.first_token_block,
            # the request's rng base as portable key data: a resume on a
            # replica sharing the fleet rng base derives the same key via
            # _req_key, but the stamp makes the park self-contained
            "rng_key": np.asarray(
                jax.random.key_data(self._req_key(rid))).tolist(),
        }
        manifest_id, _verdict = self.park_store.park(
            rid, state, payloads, tp_degree=tp_degree(),
            page_dtype=self._page_dtype())
        # durable write landed — commit the eviction: prefix-index entries
        # first (purge captures the slot's page list before retire frees
        # it), then device state, then every host mirror and pin
        pkv.purge_conversation(slot, tokens=covered, ns=req.adapter)
        self.lm.retire(self.session, np.asarray([slot], np.int32))
        self.slots[slot] = None
        self._active[slot] = False
        self._done[slot] = False
        self._adapter_idx[slot] = 0
        self._release_adapter(req)
        self._release_grammar(req)
        self._gidx[slot] = 0
        self._gstate[slot] = 0
        self._staged.pop(slot, None)
        self._out.pop(rid, None)
        self._decode_since.pop(rid, None)
        self._parked[rid] = {
            "req": req,
            "state": state,
            "manifest_id": manifest_id,
            "parked_block": int(self.blocks),
            # wall stamps survive for in-process resume continuity (the
            # completion's token_ts); a cross-process resume re-stamps
            "out_ts": self._out_ts.pop(rid, []),
            "last_tok_ts": self._last_tok_ts.pop(rid, None),
            "submit_ts": self._submit_ts.pop(rid, None),
        }
        self.stats["parked"] += 1
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._m_park.observe(dt_ms)
        if self.tracer.enabled:
            self.tracer.instant(
                "park", ("req", rid), block=self.blocks,
                args={"slot": int(slot), "pages": n_copy,
                      "generated": len(generated),
                      "manifest": manifest_id, "ms": round(dt_ms, 3)})
            self.tracer.instant(
                "tier:park", (self.lane, "tier"), block=self.blocks,
                args={"rid": rid, "pages": n_copy,
                      "manifest": manifest_id})
        return "parked"

    def _sweep_idle_parks(self) -> None:
        """Idle detection on the virtual block clock (deterministic — the
        trace's stand-in for user think-time): a decoding stream that has
        run ``park_idle_blocks`` blocks since it (re)entered decode is
        parked at the top of the scheduling round, a designated sync
        point. Resume is explicit (``submit(resume=rid)``) — parked
        conversations never block drain."""
        if self.park_store is None or not self.park_idle_blocks:
            return
        for slot, req in enumerate(self.slots):
            if (req is None or slot in self._prefilling
                    or self._done[slot]):
                continue
            since = self._decode_since.setdefault(req.request_id,
                                                  self.blocks)
            if self.blocks - since >= self.park_idle_blocks:
                self.park(req.request_id)

    def _park_deferred(self, rid: int, reason: str) -> "Rejected":
        """Structured can't-resume-RIGHT-NOW verdict: the parked record is
        untouched (still durable, still resumable) — retry after the pool
        estimate. Not a shed: nothing was lost."""
        rej = Rejected(
            request_id=rid,
            retry_after_blocks=max(self._pool_retry_after(), 1),
            queue_depth=self.queue.arrived_count(self.blocks),
            reason=reason)
        if self.tracer.enabled:
            self.tracer.instant(
                "park_defer", ("req", rid), block=self.blocks,
                args={"reason": reason,
                      "retry_after_blocks": rej.retry_after_blocks})
        return rej

    def _resume_degraded(self, rid: int, st: Optional[dict],
                         reason: str, corrupt: bool) -> Union[int, "Rejected"]:
        """The degradation ladder's landing: re-prefill via the replay
        path, bit-identical to a cold stream per the rng contract. ``st``
        is the best surviving state (durable park state, recovered state
        shard, or the in-process record); None at every rung means the
        conversation is unresumable — a structured reject, never a guess."""
        rec = self._parked.get(rid)
        if st is None and rec is not None:
            st = rec["state"]
        if st is None:
            rej = Rejected(
                request_id=rid, retry_after_blocks=0,
                queue_depth=self.queue.arrived_count(self.blocks),
                reason="park_unresumable")
            self.rejected.append(rej)
            self.stats["rejected"] += 1
            self.stats["park_rejects"] += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "reject", ("req", rid), block=self.blocks,
                    args={"reason": "park_unresumable", "cause": reason})
            return rej
        delta = self.blocks - int(st.get("parked_block", self.blocks))
        req = self._parked_request(st, delta)
        generated = [int(t) for t in st.get("generated", [])]
        self.stats["park_replays"] += 1
        if self.tracer.enabled:
            if corrupt:
                self.tracer.instant(
                    "tier:park_corrupt", (self.lane, "tier"),
                    block=self.blocks, args={"rid": rid, "cause": reason})
            self.tracer.instant(
                "tier:park_degraded", (self.lane, "tier"),
                block=self.blocks, args={"rid": rid, "cause": reason})
        # corrupt/torn stores were already quarantined (forensic record
        # kept); a clean-but-unusable park (state-only, foreign framing)
        # is consumed — drop the durable copy so ids can be reused
        if not corrupt:
            self.park_store.remove(rid)
        self._parked.pop(rid, None)
        return self.resume(req, generated)

    def resume_parked(self, request_id: int) -> Union[int, "Rejected"]:
        """Resume a parked conversation without re-prefill: load + verify
        the durable record, re-adopt its KV pages into a free slot, and
        re-enter decode at the exact interruption point — the next sampled
        token is bit-identical to an uninterrupted run (the stream-state
        invariant restores ``_tok``/``_gen_counts``/``_lengths`` exactly,
        and the rng key comes from the parked stamp).

        Verdicts: the request id (stream live again); ``Rejected`` with
        ``reason="park_deferred"`` (no free slot / pool or pin pressure —
        the park record is untouched, retry later); ``Rejected`` with
        ``reason="park_unresumable"`` (no durable record and no in-process
        record). Every integrity failure degrades to the replay path
        (:meth:`_resume_degraded`) — never a wrong token."""
        from neuronx_distributed_tpu.inference.conversation_tier import (
            ParkError, ParkIntegrityError)
        from neuronx_distributed_tpu.inference.partition import tp_degree

        if self.park_store is None:
            raise ValueError(
                "resume requires park_dir/park_store at construction")
        if self.role == "prefill":
            raise ValueError(
                "a prefill worker cannot resume decode streams — route "
                "resumes to a decode-capable worker")
        rid = int(request_id)
        # designated sync point: page adoption + mirror install must land
        # on a true block boundary, nothing in flight and no reply pending
        self._flush()
        self._retire_finished()
        t0 = time.perf_counter()
        try:
            parked = self.park_store.load(rid)
        except ParkIntegrityError as e:
            # torn or corrupt: the store quarantined it; the state shard
            # may still verify independently — the middle rung
            return self._resume_degraded(
                rid, self.park_store.recover_state(rid),
                reason=str(e), corrupt=True)
        except ParkError as e:
            # read fault (transient storage, or injected): degrading to
            # re-prefill is always safe and keeps the outcome deterministic
            return self._resume_degraded(
                rid, self.park_store.recover_state(rid),
                reason=str(e), corrupt=False)
        st = parked.state
        if parked.payloads is None:
            return self._resume_degraded(rid, st, reason="state_only_park",
                                         corrupt=False)
        if parked.tp_degree != tp_degree():
            return self._resume_degraded(
                rid, st, reason=f"tp_mismatch:{parked.tp_degree}",
                corrupt=False)
        if parked.page_dtype != self._page_dtype():
            return self._resume_degraded(
                rid, st, reason=f"page_dtype_mismatch:{parked.page_dtype}",
                corrupt=False)
        generated = [int(t) for t in st["generated"]]
        length = int(st["length"])
        prompt = np.asarray(st["prompt"], np.int32)
        covered = [int(t) for t in prompt] + generated[:-1]
        pkv = self.session.paged
        if (length != len(covered) or not generated
                or len(parked.payloads) != -(-length // pkv.page_size)):
            # the manifest verified but the state is inconsistent with the
            # page framing — structurally unusable, re-prefill
            return self._resume_degraded(rid, st, reason="state_mismatch",
                                         corrupt=False)
        delta = self.blocks - int(st["parked_block"])
        rec = self._parked.get(rid)
        req = self._parked_request(st, delta)
        free = self._free_slots()
        if not free:
            return self._park_deferred(rid, "park_deferred")
        if not self._pool_can_admit(prompt.size, req.max_new_tokens):
            self._note_pool_pressure([req])
            return self._park_deferred(rid, "park_deferred")
        # pins BEFORE page work (the adopt_handoff discipline); a deferral
        # at any rung releases everything taken so far — the parked record
        # stays whole and nothing leaks
        if self.lora and req.adapter is not None \
                and rid not in self._adapter_pins:
            try:
                self.session.adapters.acquire(req.adapter)
                self._adapter_pins[rid] = req.adapter
            except (AdapterPoolExhausted, AdapterLoadError):
                return self._park_deferred(rid, "park_deferred")
        gslot = 0
        if self.grammar and req.grammar is not None:
            if rid not in self._grammar_pins:
                try:
                    self.session.grammars.acquire(req.grammar)
                    self._grammar_pins[rid] = req.grammar
                except (GrammarPoolExhausted, GrammarLoadError):
                    self._release_adapter(req)
                    return self._park_deferred(rid, "park_deferred")
            gslot = self.session.grammars.slot_of(req.grammar)
        slot = free[0]
        try:
            pkv.adopt_pages(
                slot, covered, parked.payloads, self._write_pages_bytes,
                prompt.size + req.max_new_tokens + self._reserve_slack(),
                ns=req.adapter)
        except PagePoolExhausted:
            self._release_adapter(req)
            self._release_grammar(req)
            self._note_pool_pressure([req])
            return self._park_deferred(rid, "park_deferred")
        self.session.cache = _set_block_tables(self.session.cache,
                                               pkv.tables)
        self.session.cache = _set_cache_index_rows(
            self.session.cache, [slot], [length])
        self._next_id = max(self._next_id, rid + 1)
        self.slots[slot] = req
        now = time.perf_counter()
        self._out[rid] = list(generated)
        if rec is not None and rec.get("out_ts"):
            self._out_ts[rid] = list(rec["out_ts"])
            self._last_tok_ts[rid] = (rec.get("last_tok_ts")
                                      or rec["out_ts"][-1])
        else:
            self._out_ts[rid] = [now] * len(generated)
            self._last_tok_ts[rid] = now
        if rec is not None and rec.get("submit_ts") is not None:
            self._submit_ts[rid] = rec["submit_ts"]
        self._lengths[slot] = length
        self.session.lengths[slot] = length
        self.session.active[slot] = True
        self._active[slot] = True
        self._done[slot] = False
        self._eos[slot] = (-1 if req.eos_token_id is None
                           else req.eos_token_id)
        self._temp[slot] = req.temperature
        self._greedy[slot] = req.greedy
        # the stream-state invariant, restored exactly: generated[-1] is
        # the last sampled token, held unfed — the next block feeds it;
        # gen_counts makes the device's next draw fold_in(key, len(gen)),
        # precisely the draw an uninterrupted run would take next
        self._tok[slot] = int(generated[-1])
        self._slot_keys = self._slot_keys.at[slot].set(
            jax.random.wrap_key_data(
                jnp.asarray(st["rng_key"], jnp.uint32)))
        self._gen_counts[slot] = len(generated)
        self._adapter_idx[slot] = self._adapter_slot(req)
        self._gidx[slot] = gslot
        # recomputed from the delivered tokens — can never drift from the
        # parked stamp (which load() verified, but the walk is authoritative)
        self._gstate[slot] = (self._grammar_walk(req.grammar, 0, generated)
                              if gslot else 0)
        self._gbudget[slot] = req.max_new_tokens
        if self.async_loop:
            self._staged[slot] = None
        self.stats["resumed"] += 1
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._m_park_resume.observe(dt_ms)
        if self.tracer.enabled:
            self.tracer.instant(
                "resume", ("req", rid), block=self.blocks, ts=now,
                args={"slot": int(slot), "pages": len(parked.payloads),
                      "generated": len(generated),
                      "parked_blocks": delta, "ms": round(dt_ms, 3)})
            self.tracer.instant(
                "tier:resume", (self.lane, "tier"), block=self.blocks,
                args={"rid": rid, "pages": len(parked.payloads),
                      "parked_blocks": delta})
        # the durable record is consumed — a second resume of the same id
        # must come from a NEW park, not replay a stale one
        self.park_store.remove(rid)
        self._parked.pop(rid, None)
        self._decode_since[rid] = self.blocks
        return rid

    def parked_ids(self) -> List[int]:
        """Ids resumable from the durable store right now (the restart
        recovery surface) merged with this process's in-memory park
        records — ``submit(resume=rid)`` accepts any of them."""
        ids = set(self._parked)
        if self.park_store is not None:
            ids.update(self.park_store.list_parked())
        return sorted(ids)

    def extract_queued(self) -> List[Request]:
        """Remove and return every queued (not yet admitted) request — the
        drain path's migration source. No completions are recorded; the
        caller re-places the requests elsewhere."""
        out = list(self.queue)
        self.queue.clear()
        self._m_queue.set(0)
        for r in out:
            self._release_adapter(r)   # the pin migrates with the request
            self._release_grammar(r)
        return out

    def extract_prefilling(self) -> List[Request]:
        """Abort every in-flight chunked admission (atomic page rollback —
        the cancel machinery) and return the requests for re-placement.
        Spent chunk work is discarded; correctness never depends on it.
        Adapter pins move WITH the work: released here, re-taken by the
        destination replica's admission."""
        out = []
        for slot in list(self._prefilling):
            req = self._prefilling[slot].req
            out.append(req)
            self._abort_prefill(slot, requeue=False)
            self._release_adapter(req)
            self._release_grammar(req)
        return out

    def extract_replays(self) -> List[Tuple[Request, List[int]]]:
        """Remove and return pending recovery replays as (request,
        generated-so-far) pairs — drained replicas hand them to peers
        (adapter pins released here, re-taken at the destination)."""
        out = [(req, list(gen)) for req, gen, _ts in self._replay_q]
        self._replay_q.clear()
        self._replay_tokens = 0
        for req, _gen in out:
            self._release_adapter(req)
            self._release_grammar(req)
        return out

    def has_decode_work(self) -> bool:
        """True while any slot still runs (decoding or mid-prefill) or a
        recovery replay is pending — the Router's drain-completion gate.
        Async: a dispatched-but-unfetched block or an unsettled deferred
        first token is work too (its emissions are not recorded yet)."""
        return (bool(self._replay_q) or bool(self._prefilling)
                or bool(self._inflight) or bool(self._first_pending)
                or any(r is not None for r in self.slots))

    # --- snapshot / restore ------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-serializable block-boundary state capture: scheduler config,
        rng base, and every live request's (prompt, generated tokens,
        deadlines, chunk progress). Completed requests are NOT included —
        their streams were already delivered. Pair with
        :meth:`from_snapshot`; take it between blocks (``run`` does, via
        ``snapshot_path``)."""
        if self._sim:
            raise ValueError(
                "sim engines have no rng/device state to snapshot")
        # the snapshot serializes _out (delivered-so-far) per stream; drain
        # the async pipeline and the pending replies so the capture is a
        # true block boundary — the restored engine replays prompt+generated
        # and resumes bit-identical, and a one-token request whose reply was
        # on the device is completed, not lost.
        # The drain may latch done for streams that finished in flight:
        # retire them NOW (exactly what the next scheduling pass would do)
        # or the snapshot would encode an already-complete stream as
        # "decoding" and the restore would decode past its budget
        self._flush()
        self._retire_finished()

        def enc(r: Request, state: str, generated: List[int]) -> dict:
            # constrained streams carry (grammar name, DFA state): the
            # state is recomputable from the generated tokens (and the
            # restore path recomputes it — it can never drift), recorded
            # here so a snapshot reader sees where the stream stood
            gstate = None
            if r.grammar is not None and self.grammar:
                try:
                    gstate = self._grammar_walk(r.grammar, 0, generated)
                except (KeyError, ValueError):
                    gstate = None
            return {
                "grammar": r.grammar,
                "grammar_state": gstate,
                "request_id": int(r.request_id),
                "prompt": [int(t) for t in r.prompt],
                "max_new_tokens": int(r.max_new_tokens),
                "eos_token_id": (None if r.eos_token_id is None
                                 else int(r.eos_token_id)),
                "temperature": float(r.temperature),
                "greedy": bool(r.greedy),
                "arrival_block": int(r.arrival_block),
                "ttft_deadline_block": r.ttft_deadline_block,
                "deadline_block": r.deadline_block,
                "generated": [int(t) for t in generated],
                "state": state,
                "tenant": r.tenant,
                "adapter": r.adapter,
            }

        reqs = []
        for slot, r in enumerate(self.slots):
            if r is None:
                continue
            if slot in self._prefilling:
                d = enc(r, "prefill", [])
                # chunk progress is recorded for observability; the restore
                # re-prefills from scratch (the pages died with the process)
                d["prefill_written"] = int(self._prefilling[slot].written)
                reqs.append(d)
            else:
                reqs.append(enc(r, "decoding", self._out[r.request_id]))
        for req, pregen, _ts in self._replay_q:
            reqs.append(enc(req, "decoding", pregen))
        for r in self.queue.ordered():
            reqs.append(enc(r, "queued", []))
        return {
            "version": 1,
            "blocks": int(self.blocks),
            "next_id": int(self._next_id),
            "rng": np.asarray(jax.random.key_data(self.rng)).tolist(),
            "config": {
                "block_steps": self.block_steps,
                "fused": self.fused,
                "prefill_chunk_tokens": self.prefill_chunk_tokens,
                "top_k": self.slot_sampler.top_k,
                "top_p": self.slot_sampler.top_p,
                "pad_token_id": self.pad_token_id,
                "max_queue": self.max_queue,
                "shed_policy": self.shed_policy,
                "block_time_ms": self.block_time_ms,
                "dispatch_retries": self.dispatch_retries,
                "host_tier_pages": self.host_tier_pages,
                "paged": self.paged,
                "async_loop": self.async_loop,
                "park_idle_blocks": self.park_idle_blocks,
                "park_dir": (self.park_store.dirname
                             if self.park_store is not None else None),
            },
            # tier CONTENT is deliberately dropped (host buffers die with
            # the process, exactly like device pages); the knob above makes
            # the restored engine re-enable an empty tier, and the replay
            # path re-prefills — bit-identical either way (test-pinned)
            "requests": reqs,
            # parked conversations ride by MANIFEST ID, not content — the
            # durable copy lives in the park store; the request/generated
            # record here is the degradation ladder's last rung (a torn
            # park resumes via replay from exactly this)
            "parked": [dict(enc(rec["req"], "parked",
                                rec["state"]["generated"]),
                            manifest_id=rec["manifest_id"],
                            parked_block=rec["parked_block"],
                            start_block=rec["state"].get("start_block"),
                            first_token_block=rec["state"].get(
                                "first_token_block"))
                       for _rid, rec in sorted(self._parked.items())],
        }

    def save_snapshot(self, path: str) -> None:
        """Crash-safe snapshot write (tmp + atomic rename): a reader never
        sees a half-written file, so a crash DURING the snapshot leaves the
        previous one intact."""
        with self.tracer.span("snapshot_save", (self.lane, "snapshot"),
                              block=self.blocks):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.snapshot(), f)
            os.replace(tmp, path)

    @classmethod
    def from_snapshot(cls, lm: CausalLM, snap: Union[dict, str],
                      adapters: Optional[dict] = None,
                      grammars: Optional[dict] = None,
                      **overrides) -> "ServeEngine":
        """Rebuild an engine from a :meth:`snapshot` (dict or file path) on
        a fresh session: queued requests re-enter the queue with their
        original ids and deadlines; in-flight requests replay
        prompt+generated through the prefill path and resume BIT-IDENTICAL
        at the interruption point. ``overrides`` patch scheduler knobs
        (e.g. ``fused=False`` restores into the stepwise oracle — streams
        are schedule-independent, so that is still exact)."""
        if isinstance(snap, str):
            with open(snap) as f:
                snap = json.load(f)
        if snap.get("version") != 1:
            raise ValueError(f"unknown snapshot version {snap.get('version')}")
        cfg = dict(snap.get("config", {}))
        cfg.pop("paged", None)   # informational: the lm decides the mode
        if not getattr(lm, "paged", False):
            # restoring a tiered snapshot into a contiguous oracle: the
            # tier knob has no meaning there (streams are identical anyway)
            cfg.pop("host_tier_pages", None)
            # ... and neither does parking (pages are the park unit);
            # parked entries below degrade to replays — cold-identical
            cfg.pop("park_idle_blocks", None)
            cfg.pop("park_dir", None)
        if cfg.get("park_dir") is None:
            cfg.pop("park_dir", None)
            if "park_store" not in overrides:
                cfg.pop("park_idle_blocks", None)
        cfg.update(overrides)
        if not cfg.get("fused", True):
            # restoring into the stepwise oracle: the pipeline knob only
            # exists on the fused path (streams are identical anyway)
            cfg.pop("async_loop", None)
        rng = jax.random.wrap_key_data(
            jnp.asarray(snap["rng"], jnp.uint32))
        eng = cls(lm, rng=rng, **cfg)
        # adapter WEIGHTS are not snapshotted (like device pages, the pool
        # dies with the process): ``adapters`` re-registers {name:
        # (lora_params, lora_config)} so the replays below can re-pin
        if adapters:
            for name, (lp, lc) in adapters.items():
                eng.register_adapter(name, lp, lc)
        # grammar TABLES are not snapshotted either (compilation is
        # deterministic): ``grammars`` re-registers {name: {"regex": ...} |
        # {"json_schema": ...}} so constrained replays re-pin and the walk
        # restores each stream's DFA state from its delivered tokens
        if grammars:
            for name, spec in grammars.items():
                eng.register_grammar(name, **spec)
        eng.blocks = int(snap["blocks"])
        eng._next_id = int(snap["next_id"])
        for rd in snap["requests"]:
            req = Request(
                request_id=int(rd["request_id"]),
                prompt=np.asarray(rd["prompt"], np.int32),
                max_new_tokens=int(rd["max_new_tokens"]),
                eos_token_id=rd["eos_token_id"],
                temperature=float(rd["temperature"]),
                greedy=bool(rd["greedy"]),
                arrival_block=int(rd["arrival_block"]),
                submit_block=eng.blocks,
                ttft_deadline_block=rd.get("ttft_deadline_block"),
                deadline_block=rd.get("deadline_block"),
                tenant=rd.get("tenant", "default"),
                adapter=rd.get("adapter"),
                grammar=rd.get("grammar"),
            )
            if rd["state"] == "decoding":
                eng._replay_q.append(
                    (req, [int(t) for t in rd["generated"]], []))
                eng._replay_tokens += req.max_new_tokens
            else:
                # mid-prefill admissions restart from the queue (listed
                # before queued entries, so they keep admission priority)
                eng.queue.append(req)
            eng.stats["restored_requests"] += 1
        for rd in snap.get("parked", []):
            req = Request(
                request_id=int(rd["request_id"]),
                prompt=np.asarray(rd["prompt"], np.int32),
                max_new_tokens=int(rd["max_new_tokens"]),
                eos_token_id=rd["eos_token_id"],
                temperature=float(rd["temperature"]),
                greedy=bool(rd["greedy"]),
                arrival_block=int(rd["arrival_block"]),
                submit_block=eng.blocks,
                ttft_deadline_block=rd.get("ttft_deadline_block"),
                deadline_block=rd.get("deadline_block"),
                tenant=rd.get("tenant", "default"),
                adapter=rd.get("adapter"),
                grammar=rd.get("grammar"),
            )
            generated = [int(t) for t in rd["generated"]]
            state = {k: rd.get(k) for k in (
                "request_id", "prompt", "max_new_tokens", "eos_token_id",
                "temperature", "greedy", "arrival_block",
                "ttft_deadline_block", "deadline_block", "tenant",
                "adapter", "grammar", "grammar_state", "generated",
                "start_block", "first_token_block")}
            state["parked_block"] = int(rd.get("parked_block", eng.blocks))
            state["length"] = len(rd["prompt"]) + len(generated) - 1
            if eng.park_store is None:
                # the restored engine has no durable store: the parked
                # record can only re-prefill — schedule it now, which the
                # rng contract keeps cold-identical
                eng._replay_q.append((req, generated, []))
                eng._replay_tokens += req.max_new_tokens
            else:
                # referenced by manifest id: resume_parked loads + verifies
                # the durable copy; a torn/corrupt one replays from this
                # record (the snapshot IS the last rung of the ladder)
                eng._parked[req.request_id] = {
                    "req": req, "state": state,
                    "manifest_id": rd.get("manifest_id"),
                    "parked_block": state["parked_block"],
                    "out_ts": [], "last_tok_ts": None, "submit_ts": None}
            eng.stats["restored_requests"] += 1
        if eng.tracer.enabled:
            eng.tracer.instant(
                "restore", (eng.lane, "snapshot"), block=eng.blocks,
                args={"requests": len(snap["requests"])
                      + len(snap.get("parked", []))})
        eng._drain_replays()
        return eng

    def _record(self, slot: int, token: int, ts: float,
                block: Optional[int] = None) -> None:
        """Append one emitted token to the slot's request; latch done on EOS
        or exhausted budget (the host half of the retire-on-EOS contract).
        ``block`` overrides the virtual-block stamp on the token instant —
        the async loop harvests block t's emissions one iteration later and
        must stamp them with the block that EMITTED them."""
        req = self.slots[slot]
        if req is None or self._done[slot]:
            return
        delivered = self._deliver(req, token, ts, block)
        if req.eos_token_id is not None and token == req.eos_token_id:
            self._done[slot] = True
            self._finish_reason.setdefault(req.request_id, "eos")
        if delivered >= req.max_new_tokens:
            self._done[slot] = True
            self._finish_reason.setdefault(req.request_id, "budget")

    def _deliver(self, req: Request, token: int, ts: float,
                 block: Optional[int] = None) -> int:
        """One token onto ``req``'s stream, stamped ``ts``: what of
        :meth:`_record` needs no slot. Returns the stream's length."""
        out = self._out[req.request_id]
        out.append(token)
        self._out_ts[req.request_id].append(ts)
        self._emitted.add(req.request_id)
        # delivery-gap surface: tokens of one fused fetch share a stamp, so
        # only cross-delivery gaps (ts advanced) are observed — the user-
        # experienced inter-token latency (replay.run_trace's filter)
        last = self._last_tok_ts.get(req.request_id)
        if last is not None and ts > last:
            self._m_itl.observe((ts - last) * 1e3)
        self._last_tok_ts[req.request_id] = ts
        if self.tracer.enabled:
            self.tracer.instant(
                "tok", ("req", req.request_id),
                block=self.blocks if block is None else block, ts=ts,
                args={"t": int(token), "i": len(out) - 1})
        return len(out)

    def _complete_released(self, req: Request, token: int, ts: float,
                           block: int) -> None:
        """The reply of a request that gave its slot back when its insert
        was dispatched (:meth:`_insert_group`: a budget of one token): the
        token is its whole stream, and its completion is the one the round
        that inserted it (``block``) would have built."""
        rid = req.request_id
        self._out[rid], self._out_ts[rid] = [], []
        self._deliver(req, token, ts, block)
        if req.eos_token_id is not None and token == req.eos_token_id:
            self._finish_reason[rid] = "eos"
        elif self.grammar and req.grammar is not None:
            # the mirror of _advance_grammar, from the START state
            dfa = self.session.grammars.grammar(req.grammar)
            nxt = dfa.walk(0, token)
            if nxt >= 0 and dfa.terminal[nxt]:
                self._finish_reason[rid] = "grammar_accept"
        self._emit_completion(self._completion_of(req, block=block))

    def _retire_finished(self) -> None:
        finished = [i for i, r in enumerate(self.slots)
                    if r is not None and i not in self._prefilling
                    and self._done[i]]
        if not finished:
            return
        self.lm.retire(self.session, np.asarray(finished, np.int32))
        for slot in finished:
            self._complete_slot(slot)

    # --- the block loop --------------------------------------------------

    def _observe_block(self) -> None:
        """Per-block level sampling (host-side, one call per scheduling
        round): arrived backlog depth and — in paged mode — page-pool
        occupancy, as gauges plus Perfetto counter tracks when tracing."""
        depth = self.queue.arrived_count(self.blocks)
        self._m_queue.set(depth)
        self._m_dropped.set(self.tracer.dropped)
        tr_on = self.tracer.enabled
        if tr_on:
            self.tracer.counter("queue_depth", (self.lane, "queue"), depth,
                                block=self.blocks)
        if self.paged and self.session.paged is not None:
            pkv = self.session.paged
            in_use = pkv.allocator.in_use()
            self._m_pool.set(in_use)
            if tr_on:
                self.tracer.counter("pages_in_use", ("cache", "pool"),
                                    in_use, block=self.blocks)
                if pkv.tier is not None:
                    self.tracer.counter("tier_pages", ("cache", "tier"),
                                        pkv.tier_pages(), block=self.blocks)
        if self.lora:
            # resident-adapter counter track (Perfetto) + gauge refresh —
            # the "adapter_pool_pages" name mirrors pages_in_use: a slot is
            # the pool's allocation unit exactly like a KV page
            pool = self.session.adapters
            if tr_on:
                self.tracer.counter("adapter_pool_pages",
                                    ("cache", "adapter"), pool.in_use(),
                                    block=self.blocks)
        if self.grammar and tr_on:
            self.tracer.counter("grammar_pool_slots", ("cache", "grammar"),
                                self.session.grammars.in_use(),
                                block=self.blocks)
        if self._slo is not None:
            fired = self._slo.observe_block(self.blocks)
            if fired and self.incident is not None:
                self.incident.trigger(
                    "slo_burn", self.blocks,
                    details={"alerts": fired},
                    state=self.state_summary(), slo=self.slo_status())
        if self.incident is not None:
            self._detect_bursts()

    def _detect_bursts(self) -> None:
        """Windowed burst detectors for the flight recorder: N deadline
        misses (or N pool-pressure episodes) inside the trailing window is
        an incident, one miss is Tuesday. The recorder's per-kind gap
        rate-limits a sustained storm to one bundle per window."""
        lo = self.blocks - self._burst_window
        misses = sum(1 for b in self._miss_blocks if b > lo)
        if misses >= self._burst_threshold:
            if self.incident.trigger(
                    "deadline_miss_burst", self.blocks,
                    details={"misses_in_window": misses,
                             "window_blocks": self._burst_window,
                             "expired_total": self.stats["expired"],
                             "rejected_total": self.stats["rejected"]},
                    state=self.state_summary(), slo=self.slo_status()):
                self._miss_blocks.clear()
        storms = sum(1 for b in self._pool_pressure_blocks if b > lo)
        if storms >= self._burst_threshold:
            if self.incident.trigger(
                    "pool_exhaustion_storm", self.blocks,
                    details={"episodes_in_window": storms,
                             "window_blocks": self._burst_window,
                             "deferred_total":
                                 self.stats["deferred_admissions"]},
                    state=self.state_summary(), slo=self.slo_status()):
                self._pool_pressure_blocks.clear()

    def _fetch(self, arr, block: Optional[int] = None) -> np.ndarray:
        """The block's host fetch, as an observable span: device->host copy
        of the emitted token matrix (the 2nd of the <= 2 host ops per fused
        block). ``block`` stamps the span with the block being fetched —
        the async loop fetches block t while the counter already reads t+1.
        The fetch/dispatch span pairing on this lane is the measured half
        of the zero-host-blocking contract (``interblock_gaps``)."""
        get = jax.device_get if isinstance(arr, tuple) else np.asarray
        if not self.tracer.enabled:
            return get(arr)
        t0 = time.perf_counter()
        out = get(arr)
        # where the sync loop's harvest phase begins
        self._tile_at = time.perf_counter()
        self.tracer.complete("fetch", (self.lane, "dispatch"), t0,
                             self._tile_at,
                             block=self.blocks if block is None else block)
        return out

    def _count_block_sums(self, sums) -> None:
        """What one fused block returned beside its rows
        (``CausalLM.compile_session_decode_fused``): the cache slots its live
        steps read of a row, the number of those steps and the slots read
        over the rows of each step's rung, then a model with experts' routing
        sums."""
        walked, *routing = sums
        self.stats["kv_walk_tokens"] += int(walked[0])
        self.stats["kv_walk_steps"] += int(walked[1])
        self.stats["kv_walk_row_slots"] += int(walked[2])
        for name, value in zip(getattr(self.lm, "walk_sum_names", ()), walked[3:]):
            self.stats[name] += int(value)
        if routing:
            self._count_routing(routing[0])

    def _count_routing(self, sums) -> None:
        """One fused block's routing sums into ``stats``."""
        touched, assigned, layer_steps, *more = (int(x) for x in sums)
        self.stats["moe_experts_touched"] += touched
        self.stats["moe_assignments"] += assigned
        self.stats["moe_layer_steps"] += layer_steps
        self.stats["moe_assignments_routed"] += more[0] if more else assigned
        self.stats["moe_zero_picks"] += more[1] if len(more) > 1 else 0

    def _count_insert_routing(self, sums) -> None:
        """One paged insert's routing sums into ``stats``."""
        # (3 routing sums, grouped rows, rows the kernel multiplied; where a
        # share is held 4 routing sums, a fifth where some experts cost
        # nothing, and, last, the passes)
        touched, assigned, layer_calls, *rest = (int(x) for x in sums)
        *picks, rows, multiplied, passes = (
            rest if len(rest) > 2 else (assigned, *rest, layer_calls))
        self.stats["moe_insert_assignments_routed"] += picks[0]
        self.stats["moe_insert_zero_picks"] += sum(picks[1:])
        self.stats["moe_insert_experts_touched"] += touched
        self.stats["moe_insert_assignments"] += assigned
        self.stats["moe_insert_layer_calls"] += layer_calls
        self.stats["moe_insert_rows"] += rows
        self.stats["moe_insert_rows_multiplied"] += multiplied
        self.stats["moe_insert_passes"] += passes

    def step_block(self) -> bool:
        """One scheduling round: drain recovery replays, admit (expire/shed
        first), spend the prefill-chunk budget, advance every active slot
        ``block_steps`` tokens, record emissions, expire past-deadline
        streams, retire finished slots. Returns False when there is nothing
        left to do at the current virtual time, and never while a reply is
        still on the device: an insert dispatched with nothing decoding
        leaves its first tokens there (:meth:`_insert_group`), the round
        fetches every such insert but the newest it dispatched itself
        (:meth:`_step_block_sync`), and a round that ends with one
        unfetched, or that began with one and fetched it, answers True: so
        ``run()`` and a caller's drain call again, the next round (which
        fetches whatever it did not dispatch) brings the reply, and a
        caller that reads completions only after a True sees it. A
        one-token request's slot is free from its insert's dispatch; every
        other slot from the round its stream ends in.

        With ``async_loop=True`` the same round runs double-buffered: the
        scheduling pass commits on state as of block t-2's harvest, block t
        dispatches, and only THEN is block t-1 fetched+harvested — the
        device never waits on the host between blocks (the pipelined
        variant; same decisions, same streams — see _step_block_async)."""
        self._observed_pin = int(self.blocks)
        self._entry_inflight = len(self._inflight)
        loop = (self._step_block_async if self.async_loop
                else self._step_block_sync)
        if not self.tracer.enabled:
            return loop()
        # the round as ONE span on the phases lane, numbered like every span
        # inside it by the virtual block it started at
        decoded = self.stats["decode_blocks"]
        args: dict = {}
        with self.tracer.span("step_block", (self.lane, "phases"),
                              block=self.blocks, args=args) as rnd:
            self._tile_at = rnd.start
            worked = loop()
            args["worked"] = bool(worked)
            args["decoded"] = self.stats["decode_blocks"] > decoded
        return worked

    def _phase(self, name: str, block: int, start: Optional[float],
               args: Optional[dict] = None):
        """One phase of a round on the ``(lane, "phases")`` track. The sync
        loop's ``admit``, ``observe``, ``launch``, the dispatch lane's
        ``fetch`` and ``harvest`` follow one another and tile the round's
        ``step_block`` span: each begins on the stamp (``start``) the tile
        before it ended on, so what lies between two ``with`` blocks (a
        check, a call, freed device buffers) is the later phase's."""
        return self.tracer.span(name, (self.lane, "phases"), block=block,
                                args=args, start=start)

    def _step_block_sync(self) -> bool:
        """The synchronous block loop — the exactness oracle the async
        pipeline is tested bit-identical against.

        When the first tokens come to the host: an insert dispatched with
        rows decoding is fetched inside its ``admission`` span; one
        dispatched with nothing decoding is left on ``_first_pending``, and
        the admit phase ends by settling every pending insert except the
        newest, and the newest too unless this round dispatched it and no
        row will decode in this round (a launch reads the tokens). So a
        round that dispatched nothing fetches all, a round that dispatched
        two fetches the first, and a closed loop of one-token requests keeps
        two inserts in the device's queue: insert n+1 is planned and
        dispatched while insert n runs, then n is fetched. When a slot is
        free: a one-token row's at its insert's dispatch, any other row's
        when ``_retire_finished`` sees its stream ended. ``cancel``,
        ``park``, ``snapshot``, replay, corruption recovery and deadline
        expiry settle first (``_flush``), like the async loop's."""
        rnd = self.blocks         # the round's number, on all of its spans
        with self._phase("admit", rnd, self._tile_at) as tile:
            self._emitted.clear()     # harvest reads last block's emissions
            self.queue.advance(self.blocks)
            owed = bool(self._first_pending)    # replies this round brings
            deferred = self.stats["insert_fetches_deferred"]
            self._sweep_idle_parks()  # idle streams spill to the durable tier
            self._drain_replays()     # recovery re-enters ahead of admits
            self._admit()
            self._retire_finished()   # a 1-token budget finishes at insert
            self._admit()             # ... freeing its slot for queued work
            self._expire_prefilling()  # deadline died mid-chunk: unwind
            self._advance_prefill()   # <= prefill_chunk_tokens of prefill
            # the replies nothing waited for, all but the one insert the
            # device may still be running behind this round's host work
            self._settle_firsts(keep_newest=(
                self.stats["insert_fetches_deferred"] > deferred
                and not self._active.any()))
            self._retire_finished()   # a 1-token budget may finish at chunk end
            if self._injector is not None and self.paged:
                victims = self._injector.pages_to_corrupt(
                    self.session.paged.live_pages())
                if victims:
                    self._handle_corrupt_pages(victims)
        with self._phase("observe", rnd, tile and tile.end) as tile:
            self._observe_block()
        if not self._active.any():
            if (not self.queue and not self._prefilling
                    and not self._replay_q):
                # virtual time stands still; a reply this round brought, or
                # one still on the device for the next round to fetch, is
                # work all the same
                return owed or bool(self._first_pending)
            # nothing decoding, but arrivals, chunked prefill, or deferred
            # recovery replays pending: advance virtual time
            self.blocks += 1
            self.stats["blocks"] += 1
            return True
        t0 = time.perf_counter()
        toks, sums = self._advance_block(tile and tile.end)
        now = time.perf_counter()
        with self._phase("harvest", rnd, self._tile_at):
            if self.tracer.enabled:
                self.tracer.complete(
                    "decode_block", (self.lane, "blocks"), t0, now,
                    block=self.blocks,
                    args={"active": int(self._active.sum()),
                          "steps": self.block_steps, "fused": self.fused})
            if sums:
                self._count_block_sums(sums)
            self.stats["blocks"] += 1
            self.stats["decode_blocks"] += 1
            # mirror the device latches from the one fetch (K, b)
            for i in range(self.block_steps):
                row = toks[i]
                for slot, req in enumerate(self.slots):
                    if (req is not None and slot not in self._prefilling
                            and not self._done[slot]):
                        self._record(slot, int(row[slot]), now)
                        # DFA-state mirror: the same transition the device
                        # took on this emitted token (accept-terminal latches
                        # done + finish_reason="grammar_accept", like EOS)
                        self._advance_grammar(slot, int(row[slot]))
                self._lengths += 1
                self._gen_counts += 1
            self._tok = toks[-1].astype(np.int32)
            self.blocks += 1
            self._expire_decoding()   # completion deadline passed: partial NOW
            self._retire_finished()
        return True

    def _advance_block(self, after: Optional[float] = None
                       ) -> Tuple[np.ndarray, list]:
        """Advance the pool ``block_steps`` tokens; returns the emitted
        (K, max_batch) token matrix and, from a fused block, the sums that
        rode its fetch (``_count_block_sums``). Fused mode: ONE program call
        + ONE fetch. Stepwise mode: the same schedule paid per token (K
        dispatches + K fetches) — the measurement baseline and exactness
        oracle. Sim mode (inference/simlm.py): the stub's deterministic
        token function, pure numpy, accounted like one fused dispatch +
        fetch. The ``launch`` phase begins on the stamp ``after`` (where
        ``observe`` ended) and ends where the program call returned and the
        block's fetch begins (stepwise: it holds all K of each)."""
        said = ({"active": int(self._active.sum()), "host_args": 0}
                if self.tracer.enabled else None)
        launch = self._phase("launch", self.blocks, after, said)
        if self._sim:
            with launch:
                rids = [(-1 if r is None else r.request_id)
                        for r in self.slots]
                toks = self._dispatch(
                    "decode", lambda: self.lm.sim_decode_block(
                        self.block_steps, self._tok, self._active,
                        self._done, self._gen_counts, rids))
                self.session.lengths = self.session.lengths + self.block_steps
                self.stats["program_calls"] += 1
                self.stats["host_fetches"] += 1
            return self._fetch(toks), []
        if self.fused:
            with launch:
                fused = self.lm.compile_session_decode_fused(
                    self.block_steps, self.slot_sampler, self.pad_token_id)
                # what the block needs from the host goes up IN the call,
                # as host arrays, with no eager upload before it: the tokens
                # and latches as the mirrors they are (no copy), the six rows
                # only the host writes as one matrix in a buffer kept for it.
                # Safe in the sync loop and only there: the block is fetched
                # before harvest writes a mirror or the next launch refills
                # the buffer, so a backend that aliases a host buffer (jax's
                # CPU client does: _dispatch_block_async) still reads what
                # the scheduler decided
                rows = self.lm.block_rows(
                    self._gen_counts, self._lengths, self._active, self._eos,
                    self._temp, self._greedy, out=self._block_rows)
                args = (self.lm.params, self.session.cache,
                        self._tok[:, None], self._slot_keys, self._done, rows,
                        *self.lm._ad_args(self.session.adapters,
                                          self._adapter_idx),
                        *self.lm._gr_args(self.session.grammars, self._gidx,
                                          self._gstate, self._gbudget))
                uploads = self._count_uploads(args)
                if said is not None:
                    said["host_args"] = uploads
                # 5 outputs, or 6 with grammar (the trailing DFA state exists
                # for the async pipeline; the sync loop ignores it)
                outs = self._dispatch("decode", lambda: fused(*args))
                toks, cache = outs[0], outs[1]
                self.session.cache = cache
                self.session.lengths = self.session.lengths + self.block_steps
                self.stats["program_calls"] += 1
                self.stats["host_fetches"] += 1
            # the block's sums ride the same fetch: how far its steps read
            # the cache, then (a model with experts) what its router chose
            toks, *sums = self._fetch((toks, *outs[self._walked_at:]))
            return toks, sums
        with launch:
            toks = self._advance_stepwise()
        self._tile_at = None        # harvest begins where launch ended
        return toks, []

    def _count_uploads(self, args) -> int:
        """The host arrays among a fused block's arguments (they go up with
        the call), counted into ``stats["block_uploads"]``."""
        uploads = sum(isinstance(a, np.ndarray) for a in args)
        self.stats["block_uploads"] += uploads
        return uploads

    def _advance_stepwise(self) -> np.ndarray:
        """``_advance_block`` paid per token: K dispatches, K fetches."""
        out = np.zeros((self.block_steps, self.lm.max_batch), np.int64)
        done = self._done.copy()
        temp = jnp.asarray(self._temp)
        greedy = jnp.asarray(self._greedy)
        tok = self._tok.copy()
        lengths = self._lengths.copy()
        counts = self._gen_counts.copy()
        gstate = self._gstate.copy()
        gactive = self._gidx > 0
        gtree = (self.session.grammars.tree
                 if self.grammar and self.session.grammars is not None
                 else None)
        max_len = self.lm.config.max_seq_len
        for i in range(self.block_steps):
            sub = jax.vmap(jax.random.fold_in)(self._slot_keys,
                                               jnp.asarray(counts))
            allowed = None
            if gtree is not None:
                # same boolean math as the fused scan, on the same tables —
                # the stepwise oracle replicates the device mask exactly
                allowed = CausalLM.grammar_allowed(
                    gtree, jnp.asarray(self._gidx), jnp.asarray(gstate),
                    jnp.asarray(self._gbudget), jnp.asarray(counts))
            # direct decode call, NOT lm.step(): step() raises at the cache
            # edge, while the fused program latches done and lets the
            # (dropped) writes run out the block — the stepwise oracle must
            # replicate the device semantics exactly or the two modes would
            # diverge on requests admitted flush against max_seq_len
            logits, cache = self._dispatch(
                "decode", lambda t=tok: self.lm._decode(
                    self.lm.params, self.session.cache,
                    jnp.asarray(t[:, None], jnp.int32),
                    *self.lm._live_args(self._active & ~done),
                    *self.lm._ad_args(self.session.adapters,
                                      self._adapter_idx)))
            self.session.cache = cache
            self.session.lengths += 1
            nxt = self._fetch(self.slot_sampler(logits[:, 0], sub, temp,
                                                greedy, allowed=allowed))
            self.stats["program_calls"] += 1
            self.stats["host_fetches"] += 1
            done_before = done
            out[i] = np.where(done | ~self._active, self.pad_token_id, nxt)
            done = done | (self._active & (self._eos >= 0) & (nxt == self._eos))
            if gtree is not None:
                adv = gactive & self._active & ~done_before
                new_state = np.asarray(
                    gtree["next"])[self._gidx, gstate, nxt]
                gstate = np.where(adv, new_state, gstate)
                done = done | (adv & np.asarray(
                    gtree["terminal"])[self._gidx, gstate])
            counts = counts + 1
            lengths = lengths + 1
            done = done | (self._active & (lengths + 1 >= max_len))
            tok = nxt.astype(np.int32)
        return out

    # --- the async double-buffered pipeline (ROADMAP #22) -----------------
    # One-block pipeline depth: while block t's fused scan runs on device,
    # the host runs the whole scheduling pass and only then fetches block
    # t-1. Correctness rests on three facts. (1) Every scheduling decision
    # already commits on the virtual block clock and host mirrors — never on
    # the fetched matrix of the block being decided — so a one-block harvest
    # lag reorders NOTHING. (2) Block t+1's device inputs are block t's
    # device OUTPUTS (next-token, done, DFA-state futures chained without a
    # fetch), plus host-known per-slot overrides for rows admitted in
    # between — exactly the values the sync loop would have uploaded.
    # (3) Emissions a finished row over-produces before its (one block
    # later) retire are discarded by the same host done-latch that already
    # discards mid-block post-EOS samples in sync mode, and their cache
    # writes land in the enlarged page reserve (_reserve_slack). Streams
    # are therefore bit-identical by construction; tests/test_async_loop.py
    # pins it across the whole exactness matrix.

    def _step_block_async(self) -> bool:
        """One pipelined scheduling round. Ordering per iteration t:
        schedule (on state as of harvest t-1) -> dispatch block t ->
        fetch+harvest block t-1 (the single blocking host op, paid while
        block t runs) -> expire/retire. Designated sync points (snapshot,
        cancel, replay admission, corruption recovery, deadline expiry,
        end-of-work) drain the pipeline via _flush; between them the host
        never blocks between dispatches — the tracer's dispatch/fetch span
        gap measures exactly 0 (interblock_gaps) and the nxdcheck
        ``async-contract`` rule forbids blocking primitives on this path."""
        self._emitted.clear()
        self.queue.advance(self.blocks)
        self._sweep_idle_parks()  # sync point: park() drains the pipeline
        self._drain_replays()
        self._admit()
        self._retire_finished()
        self._admit()
        self._expire_prefilling()
        self._advance_prefill()
        self._retire_finished()
        if self._injector is not None and self.paged:
            victims = self._injector.pages_to_corrupt(
                self.session.paged.live_pages())
            if victims:
                self._handle_corrupt_pages(victims)
        self._observe_block()
        if not self._active.any():
            # nothing to dispatch: drain the pipeline (its harvest may
            # finish streams) and either terminate or advance virtual time
            self._flush()
            self._retire_finished()
            if (not self.queue and not self._prefilling
                    and not self._replay_q and not self._active.any()):
                return False
            self.blocks += 1
            self.stats["blocks"] += 1
            return True
        t0 = time.perf_counter()
        self._dispatch_block_async()
        self.stats["blocks"] += 1
        self.stats["decode_blocks"] += 1
        self._harvest_inflight()
        now = time.perf_counter()
        if self.tracer.enabled:
            self.tracer.complete(
                "decode_block", (self.lane, "blocks"), t0, now,
                block=self.blocks,
                args={"active": int(self._active.sum()),
                      "steps": self.block_steps, "fused": True,
                      "inflight": len(self._inflight)})
        self.blocks += 1
        self._expire_decoding()
        self._retire_finished()
        return True

    def _budget_done(self) -> np.ndarray:
        """Host-side prediction of per-row budget exhaustion after the
        blocks dispatched so far. The device never latches budget-done (the
        host's _record does, from the fetch) — so the pipelined dispatch
        ORs this into the carried done input, keeping block t+1's inputs
        bit-identical to what the sync loop would upload."""
        maxn = np.asarray(
            [0 if r is None else r.max_new_tokens for r in self.slots],
            np.int64)
        return self._active & (self._gen_counts >= maxn)

    def _sim_end_done(self, toks: np.ndarray,
                      done_in: np.ndarray) -> np.ndarray:
        """Sim-mode stand-in for the device's carried done latches: the
        eager sim 'dispatch' computes what the real scan would carry out of
        this block (EOS per emitted token, plus the budget OR the real
        pipeline applies at the next dispatch), so sim and real async mode
        run the SAME schedule — the sim-vs-real schedule pins hold."""
        done = done_in.copy()
        for slot, req in enumerate(self.slots):
            if (req is None or slot in self._prefilling
                    or not self._active[slot]):
                continue
            e = int(self._gen_counts[slot])
            for k in range(toks.shape[0]):
                if done[slot]:
                    break
                t = int(toks[k, slot])
                e += 1
                if req.eos_token_id is not None and t == req.eos_token_id:
                    done[slot] = True
                if e >= req.max_new_tokens:
                    done[slot] = True
        return done

    def _dispatch_block_async(self) -> None:
        """Dispatch one fused block WITHOUT fetching anything. Warm (an
        unfetched block is in flight): device inputs are the previous
        dispatch's output futures — next-token, done (ORed with the host's
        budget prediction) and DFA state chain on device. Cold (first block
        after a flush): inputs come from the host mirrors, exactly like the
        sync loop. Either way, slots admitted/adopted/replayed since the
        previous dispatch are applied LAST as per-slot overrides (host ints
        where the value is known, device gathers where the first token is
        itself still in flight). Appends the in-flight record; the matching
        fetch happens in _harvest_inflight one iteration later."""
        rids = [(-1 if (r is None or i in self._prefilling)
                 else r.request_id) for i, r in enumerate(self.slots)]
        prev = self._inflight[-1] if self._inflight else None
        if self._sim:
            done_in = (prev["end_done"] if prev is not None
                       else self._done).copy()
            for slot in self._staged:
                done_in[slot] = self._done[slot]
            all_rids = [(-1 if r is None else r.request_id)
                        for r in self.slots]
            toks = self._dispatch(
                "decode", lambda: self.lm.sim_decode_block(
                    self.block_steps, self._tok, self._active, done_in,
                    self._gen_counts, all_rids))
            rec = {"toks": toks, "rids": rids, "block": self.blocks,
                   "end_done": self._sim_end_done(toks, done_in)}
        else:
            fused = self.lm.compile_session_decode_fused(
                self.block_steps, self.slot_sampler, self.pad_token_id)
            # every host mirror is COPIED before it becomes a device input:
            # jax's CPU client zero-copy-aliases numpy buffers, and unlike
            # the sync loop (whose immediate fetch forces execution first)
            # this program is still in flight when the next scheduling pass
            # mutates the mirrors in place — the copy gives the program a
            # buffer only it owns
            if prev is None:
                tok_in = jnp.asarray(self._tok[:, None].copy())
                done_in = jnp.asarray(self._done.copy())
                gstate_in = (jnp.asarray(self._gstate.copy())
                             if self.grammar else None)
            else:
                tok_in = prev["nxt"]
                done_in = prev["done"]
                gstate_in = prev["gstate"]
                budget = self._budget_done()
                if budget.any():
                    done_in = done_in | jnp.asarray(budget)
            for slot, ov in self._staged.items():
                if ov is None:
                    # host-known row (adoption / replay / settled first):
                    # the mirrors carry the exact values
                    t_v = int(self._tok[slot])
                    d_v = bool(self._done[slot])
                    g_v = int(self._gstate[slot])
                else:
                    # deferred first token: still a device future — gather
                    # the scalar and derive done/DFA-state on device (the
                    # same latches the sync insert computed on the host)
                    t_v = ov["fut"][ov["idx"]]
                    req = self.slots[slot]
                    d_v = bool(req is not None and req.max_new_tokens <= 1)
                    eos = int(self._eos[slot])
                    if eos >= 0:
                        d_v = (t_v == eos) | d_v
                    g_v = 0
                    gi = int(self._gidx[slot])
                    if self.grammar and gi > 0:
                        tree = self.session.grammars.tree
                        g_v = tree["next"][gi, 0, t_v]
                        d_v = tree["terminal"][gi, g_v] | d_v
                tok_in = tok_in.at[slot, 0].set(t_v)
                done_in = done_in.at[slot].set(d_v)
                if gstate_in is not None:
                    gstate_in = gstate_in.at[slot].set(g_v)
            # block_rows without ``out``: a new matrix, the copy of these
            # six mirrors
            args = (self.lm.params, self.session.cache, tok_in,
                    self._slot_keys, done_in,
                    self.lm.block_rows(self._gen_counts, self._lengths,
                                       self._active, self._eos, self._temp,
                                       self._greedy),
                    *self.lm._ad_args(self.session.adapters,
                                      self._adapter_idx.copy()),
                    *self.lm._gr_args(self.session.grammars,
                                      self._gidx.copy(),
                                      gstate_in if gstate_in is not None
                                      else self._gstate.copy(),
                                      self._gbudget.copy()))
            self._count_uploads(args)
            outs = self._dispatch("decode", lambda: fused(*args))
            self.session.cache = outs[1]
            rec = {"toks": outs[0], "nxt": outs[2], "done": outs[4],
                   "gstate": outs[5] if self.grammar else None,
                   "sums": outs[self._walked_at:],
                   "rids": rids, "block": self.blocks}
        self._staged.clear()
        # the device increments lengths/counts unconditionally for every
        # row — mirror that NOW (a later admission overwrites its slot,
        # same as sync); the harvest must not advance them again
        self._lengths += self.block_steps
        self._gen_counts += self.block_steps
        self.session.lengths = self.session.lengths + self.block_steps
        self.stats["program_calls"] += 1
        self._inflight.append(rec)

    def _harvest_inflight(self, drain: bool = False) -> None:
        """Fetch+record pipelined blocks down to depth 1 (``drain`` empties
        the pipeline — the designated-sync-point path). Deferred first
        tokens settle in stream order: before the first block that includes
        their row, after the blocks that precede their admission."""
        keep = 0 if drain else 1
        while len(self._inflight) > keep:
            rec = self._inflight.popleft()
            self._settle_firsts(before_block=rec["block"])
            self._harvest_rec(rec)
        self._settle_firsts()

    def _harvest_rec(self, rec: dict) -> None:
        """Record one fetched block's emissions — the pipelined twin of the
        sync loop's harvest. Each row is gated on the request id captured
        at DISPATCH time: a slot retired and re-admitted while the block
        was in flight must not have the old row's emissions attributed to
        its new occupant. The live done-latch gate discards a finished
        row's over-produced tokens, exactly like sync's mid-block
        post-EOS discard."""
        if "sums" in rec:
            toks, *sums = self._fetch((rec["toks"], *rec["sums"]),
                                      block=rec["block"])
            self._count_block_sums(sums)
        else:                       # sim mode: nothing ran on a device
            toks = self._fetch(rec["toks"], block=rec["block"])
        self.stats["host_fetches"] += 1
        now = time.perf_counter()
        rids = rec["rids"]
        for i in range(toks.shape[0]):
            row = toks[i]
            for slot, req in enumerate(self.slots):
                if (req is not None and rids[slot] == req.request_id
                        and not self._done[slot]):
                    self._record(slot, int(row[slot]), now,
                                 block=rec["block"])
                    self._advance_grammar(slot, int(row[slot]))
        for slot, req in enumerate(self.slots):
            if req is not None and rids[slot] == req.request_id:
                self._tok[slot] = int(toks[-1, slot])

    def _settle_firsts(self, before_block: Optional[int] = None,
                       keep_newest: bool = False) -> None:
        """Fetch and record deferred first tokens, ONE fetch an insert (sim:
        host-known values whose RECORD waited for schedule parity; real:
        what the insert program left on the device). Both loops settle
        here. The async loop after the previous block's harvest, while the
        current block runs; ``before_block`` limits the pass to admissions
        at or before that block — a multi-block drain must interleave
        first-token records with the blocks that follow them, or a stream's
        token 0 would land after its token 1. The sync round
        (:meth:`_step_block_sync`) once its admit phase has dispatched what
        it could, with ``keep_newest`` leaving the newest insert on the
        device for the next round to fetch. A row that kept its slot is
        recorded into it; a request that released its slot at dispatch is
        completed (:meth:`_complete_released`). The stamp of a token is
        taken after its fetch returned."""
        if not self._first_pending:
            return
        kept = self._first_pending[-1]["insert"] if keep_newest else None
        keep: List[dict] = []
        fetched: Dict[int, np.ndarray] = {}     # one fetch an admission
        now = time.perf_counter()
        for p in self._first_pending:
            if p["insert"] == kept or (
                    before_block is not None and p["block"] > before_block):
                keep.append(p)
                continue
            if p["fut"] is None:
                tok = int(p["val"])
            else:
                if id(p["fut"]) not in fetched:
                    fetched[id(p["fut"])] = self._fetch_first(
                        p["fut"], p.get("routing"))
                    now = time.perf_counter()
                tok = int(fetched[id(p["fut"])][p["idx"]])
            slot = p["slot"]
            req = p["req"] or self.slots[slot]
            if req is None or req.request_id != p["rid"]:
                continue        # cancelled/expired before delivery
            self._observe_first_token(req, slot, now, block=p["block"],
                                      **p["seen"])
            if p["req"] is not None:
                self._complete_released(req, tok, now, p["block"])
                continue
            self._tok[slot] = tok
            self._record(slot, tok, now, block=p["block"])
            self._advance_grammar(slot, tok)
        self._first_pending = keep

    def _flush(self) -> None:
        """Drain the pipeline completely: fetch+harvest every in-flight
        block and settle every deferred first token (the sync loop has no
        block in flight: its pending replies alone). After a flush the next
        dispatch restarts cold from the host mirrors — bit-identical state
        to a sync engine at the same block boundary with nothing pending
        (which is why snapshot, cancel, park, replay, corruption recovery
        and deadline expiry run their logic unchanged after calling this,
        in either loop)."""
        self._harvest_inflight(drain=True)

    # --- observability surface -------------------------------------------

    def request_timeline(self, request_id: int) -> List[dict]:
        """The request's recorded lifecycle, oldest first: one dict per
        event with wall ``ts_ms`` (tracer epoch), the virtual ``block``,
        span ``dur_ms`` where applicable, and the event args. Empty when
        tracing was off (or the events aged out of the ring buffer) —
        enable with ``ServeEngine(trace=True)``."""
        picked = [(i, ev) for i, ev in enumerate(self.tracer.events())
                  if ev["lane"] == ("req", request_id)]
        # time order with recording order as the tiebreak: a lifecycle span
        # (e.g. 'queued') starts at an earlier stamp than the instant
        # recorded just before it
        picked.sort(key=lambda t: (t[1]["ts"], t[0]))
        out = []
        for _, ev in picked:
            d = {"name": ev["name"],
                 "ts_ms": round((ev["ts"] - self.tracer._t0) * 1e3, 3),
                 "block": ev["block"], "args": ev["args"] or {}}
            if ev["ph"] == "X":
                d["dur_ms"] = round(ev["dur"] * 1e3, 3)
            out.append(d)
        return out

    def request_attribution(self, request_id: int) -> Optional[dict]:
        """Critical-path decomposition of one request read off the tracer:
        its submit->terminal span partitioned into named phases (queued /
        pool_wait / prefill / decode / replay ...) on the virtual block
        clock, phases guaranteed to sum to the end-to-end latency. None
        when tracing was off. See ``observability/attribution.py``."""
        return _attribution.request_attribution(self.tracer, request_id)

    def attribution_report(self) -> dict:
        """Aggregate phase mix over every traced request (per-tenant and
        per-replica breakdowns included when present)."""
        return _attribution.attribution_report(self.tracer)

    def explain_deadline_miss(self, request_id: int) -> dict:
        """Name the phase that burned a missed deadline's budget — the
        PROFILE round-10 manual timeline read, automated."""
        return _attribution.explain_deadline_miss(self.tracer, request_id)

    def slo_status(self) -> Optional[dict]:
        """Per-objective compliance/burn/alert snapshot (None when the
        engine was built without ``slos``)."""
        return None if self._slo is None else self._slo.status()

    def load_summary(self) -> ReplicaLoad:
        """The engine's current load as the shared :class:`ReplicaLoad`
        struct — router placement, the autoscaler policy and the incident
        state card all read THIS instead of ad-hoc attribute pokes."""
        free = len(self._free_slots())
        backlog = (len(self.queue) + len(self._prefilling)
                   + len(self._replay_q))
        pkv = self.session.paged if self.paged else None
        pages_in_use = pkv.allocator.in_use() if pkv is not None else None
        pages_free = pkv.allocator.available() if pkv is not None else None
        retry = self._pool_retry_after()
        est = (0 if (free > len(self.queue)
                     and backlog - len(self.queue) == 0
                     and (pages_free is None or pages_free > 0))
               else retry + backlog)
        return ReplicaLoad(
            role=self.role,
            queue_depth=len(self.queue),
            prefilling=len(self._prefilling),
            replays=len(self._replay_q),
            backlog=backlog,
            active_slots=int(sum(1 for r in self.slots if r is not None)),
            free_slots=free,
            est_ttft_blocks=int(est),
            pool_retry_after_blocks=int(retry),
            inflight_tokens=int(sum(
                r.max_new_tokens - len(self._out.get(r.request_id, ()))
                for r in self.slots if r is not None)),
            queued_tokens=int(self.queue.tokens()),
            pages_in_use=pages_in_use,
            pages_free=pages_free,
            tier_pages=(pkv.tier_pages()
                        if pkv is not None and pkv.tier is not None
                        else None),
            adapters_resident=(sorted(self.session.adapters.resident)
                               if self.lora else None),
            slo_alerting=(self._slo is not None and self._slo.alerting()),
            decode_blocks=int(self.stats["decode_blocks"]),
            inserted_requests=int(self.stats["inserted_requests"]),
            # newest virtual block whose device effects this summary
            # reflects: the block the last step entered on, minus pipeline
            # depth (async_loop lags by one; an idle or sync engine is
            # fully current).  max() with the AT-ENTRY depth: a drain step
            # that harvested the final in-flight block leaves the pipeline
            # empty but its summary still only reflects through pin - 1
            # (PR 19 remainder)
            observed_block=(self._observed_pin
                            - max(len(self._inflight),
                                  self._entry_inflight)),
            parked=len(self._parked),
        )

    def state_summary(self) -> dict:
        """One JSON-able card of the scheduler's current state — the
        incident bundle's engine section (and a debugging surface in its
        own right): queue/slot occupancy, per-slot stream progress, pool
        and tier residency, the full stats counter set."""
        slots = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            slots.append({
                "slot": slot, "request_id": req.request_id,
                "tenant": req.tenant,
                "generated": len(self._out.get(req.request_id, ())),
                "max_new_tokens": req.max_new_tokens,
                "prefilling": slot in self._prefilling,
                "done": bool(self._done[slot]),
            })
        load = self.load_summary()
        out = {
            "engine": self.lane,
            "role": self.role,
            "blocks": int(self.blocks),
            "queue_depth": load.queue_depth,
            "arrived_depth": self.queue.arrived_count(self.blocks),
            "prefilling": load.prefilling,
            "replay_pending": load.replays,
            "slots": slots,
            "parked": sorted(self._parked),
            "completed": len(self.completed),
            "rejected": len(self.rejected),
            # the shared typed card (ReplicaLoad) — same struct placement
            # and the autoscaler read, nested whole so an incident bundle
            # shows exactly what the policy saw
            "load": load.to_dict(),
            "stats": dict(self.stats),
        }
        pkv = self.session.paged if self.paged else None
        if pkv is not None:
            out["pool"] = {
                "pages": pkv.num_pages,
                "in_use": pkv.allocator.in_use(),
                "free": pkv.allocator.available(),
            }
            if pkv.tier is not None:
                out["tier"] = {
                    "max_pages": pkv.tier.max_pages,
                    "resident_pages": pkv.tier_pages(),
                }
        if self.lora:
            pool = self.session.adapters
            out["adapters"] = {
                "slots": pool.n_slots,
                "resident": sorted(pool.resident),
                "pinned": {n: pool.pinned(n) for n in sorted(pool.resident)
                           if pool.pinned(n)},
            }
        if self.grammar:
            gpool = self.session.grammars
            out["grammars"] = {
                "slots": gpool.n_slots,
                "resident": sorted(gpool.resident),
                "pinned": {n: gpool.pinned(n) for n in sorted(gpool.resident)
                           if gpool.pinned(n)},
            }
        return out

    def _sync_compile_metrics(self) -> None:
        """Mirror the lm's per-program compile timings (recorded once per
        signature at compile time, engine-independent) into the registry so
        the exposition carries the compile-vs-execute split. Also the final
        refresh of the ring-buffer drop counter: retire-time events land
        AFTER the last block's sample."""
        for sig, ms in getattr(self.lm, "compile_ms", {}).items():
            self.metrics.gauge(
                "compile_ms", help="first-call XLA compile wall ms",
                program=sig).set(ms)
        # the lm's rows of the compile log, for a fleet's restart dashboard:
        # where a restart's compile wall went by part, and which programs'
        # cache was lost on a replica that should have started warm
        for row in getattr(self.lm, "compile_rows", list)():
            for part in _COMPILE_PARTS:
                if part in row:
                    self.metrics.gauge(
                        "compile_part_ms", help="a program's compile wall by part",
                        program=row["name"], part=part[:-3]).set(row[part])
            self.metrics.gauge(
                "compile_cache_misses", help="persistent-cache misses of a program's compiles",
                program=row["name"]).set(row["misses"])
        self._m_dropped.set(self.tracer.dropped)

    def run(self, max_blocks: Optional[int] = None,
            snapshot_path: Optional[str] = None,
            snapshot_every_blocks: int = 8) -> List[Completion]:
        """Drive blocks until the queue and every slot drain (or
        ``max_blocks`` elapse); returns completions in finish order.

        ``snapshot_path`` arms crash recovery: the engine writes an atomic
        :meth:`snapshot` every ``snapshot_every_blocks`` rounds and REMOVES
        it on a clean drain — so the file existing at startup means the
        previous run died mid-trace, and :meth:`from_snapshot` resumes its
        in-flight streams bit-identical."""
        every = max(int(snapshot_every_blocks), 1)
        n = 0
        while self.step_block():
            n += 1
            if snapshot_path and n % every == 0:
                self.save_snapshot(snapshot_path)
            if max_blocks is not None and n >= max_blocks:
                self._sync_compile_metrics()
                return self.completed
        if snapshot_path and os.path.exists(snapshot_path):
            os.remove(snapshot_path)   # clean drain: nothing to recover
        self._sync_compile_metrics()
        return self.completed
