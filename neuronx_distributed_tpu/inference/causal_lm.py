"""Causal-LM serving runtime (reference ``examples/inference/modules/
model_base.py`` — ``NeuronBaseModel``/``NeuronBaseForCausalLM`` with KV-cache
management, context-encoding vs token-generation model split, bucketing,
continuous-batching ``seq_ids`` — and ``runner.py``'s generate loop).

Two compiled programs over ONE weight set (the reference's CTX/TKG split):

* ``prefill`` per sequence bucket: full-sequence forward writing the KV
  cache, returns all logits;
* ``decode``: single-token step, cache donated in/out (the reference aliases
  KV state via metaneff IO aliasing; donation is the PJRT equivalent). The
  K/V leaves are ONE buffer each from the donated argument to the result:
  the fused programs' step loop carries the cache, the model's layer loop
  carries its K/V leaves (``models/llama.py::KVLayerView``), and every layer
  writes its rows in place. No program here may slice a layer's pool out of
  that buffer or copy it (``tests/test_kv_carry_structure.py``).

Continuous batching: the KV cache is a fixed pool of ``max_batch`` slots with
per-slot lengths (``cache_index`` vector); ``insert`` prefills one or more
slots while other slots keep decoding — the seq_ids reorder machinery of the
reference becomes plain slot indexing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache
from jax.experimental.layout import Format, Layout

from neuronx_distributed_tpu.inference.paged_cache import PagedKVCache
from neuronx_distributed_tpu.inference.partition import (
    leaf_partition_spec, repl_args, repl_avals, shard_avals, shard_out,
    tp_degree, zeros_like_avals,
)
from neuronx_distributed_tpu.inference.sampling import Sampler, SlotSampler
from neuronx_distributed_tpu.models.llama import (
    KV_PAGE_LEAVES,
    KV_SCALE_LEAVES,
    kv_walk,
    leaf_paths,
)
from neuronx_distributed_tpu.moe.expert_mlps import grouped_rows_multiplied, share_call_sums
from neuronx_distributed_tpu.utils.compile_cache import compile_log

PyTree = Any


def replicate_out(tree: PyTree) -> PyTree:
    """Program-boundary sharding pin: force every leaf fully replicated
    when a device mesh is active (no-op otherwise). Every compiled
    program that RETURNS a session cache / adapter / grammar collection
    must route it through this constraint — the AOT session programs are
    lowered on replicated cache avals, and an unconstrained output lets
    GSPMD hand back a sharded layout the next call rejects (the PR 3
    class; statically enforced by nxdcheck's cache-replication rule)."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return tree
    from jax.sharding import NamedSharding, PartitionSpec

    repl = NamedSharding(ps.get_mesh(), PartitionSpec())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, repl), tree)


def _set_block_tables(cache: PyTree, tables) -> PyTree:
    """Overwrite every per-layer block_table leaf (stacked (L, b, ppseq))
    with the host allocator's current tables — the ONLY cache leaves the
    host ever writes between blocks in paged mode (the pool itself moves
    exclusively through donated device programs)."""
    t = jnp.asarray(tables, jnp.int32)

    def fix(path, leaf):
        if jax.tree_util.keystr(path).endswith("['block_table']"):
            return jnp.broadcast_to(t, leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _set_cache_index(cache: PyTree, lengths: jax.Array) -> PyTree:
    """Overwrite every per-layer cache_index leaf (stacked (L, b)) with the
    true prompt lengths — pad tails beyond a slot's length are masked out."""

    def fix(path, leaf):
        if jax.tree_util.keystr(path).endswith("['cache_index']"):
            return jnp.broadcast_to(lengths.astype(leaf.dtype), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _set_cache_index_rows(cache: PyTree, slot_ids, lengths) -> PyTree:
    """Overwrite the cache_index entries of ``slot_ids`` ONLY (stacked
    (L, b) leaves) — the page-adoption install's targeted variant of
    ``_set_cache_index``: a migrated stream's slot must start decoding at
    its prompt length while every other slot's device counter (which the
    compiled programs advance) stays untouched."""

    def fix(path, leaf):
        if not jax.tree_util.keystr(path).endswith("['cache_index']"):
            return leaf
        out = leaf
        for s, v in zip(slot_ids, lengths):
            out = out.at[:, int(s)].set(jnp.asarray(int(v), leaf.dtype))
        return out

    return jax.tree_util.tree_map_with_path(fix, cache)


def _scatter_cache_rows(old: PyTree, fresh: PyTree, slots: jax.Array,
                        new_len: jax.Array, rows: int) -> PyTree:
    """Scatter ``rows`` freshly prefilled cache rows into the session cache
    at ``slots`` via per-slot ``dynamic_update_slice`` — HBM traffic scales
    with the INSERTED rows, not the whole cache (cache leaves are
    layer-stacked with batch at axis 1; ``fresh`` was prefilled at batch
    width ``rows``). ``cache_index`` rows take the true prompt lengths."""

    def upd(path, o, f):
        if jax.tree_util.keystr(path).endswith("['cache_index']"):
            for i in range(rows):
                v = jnp.broadcast_to(new_len[i].astype(o.dtype), (o.shape[0], 1))
                o = jax.lax.dynamic_update_slice_in_dim(o, v, slots[i], axis=1)
            return o
        for i in range(rows):
            o = jax.lax.dynamic_update_slice_in_dim(
                o, jax.lax.dynamic_slice_in_dim(f, i, 1, axis=1), slots[i], axis=1)
        return o

    return jax.tree_util.tree_map_with_path(upd, old, fresh)


def _state_rows(leaf: jax.Array, slots: jax.Array, starts: jax.Array) -> jax.Array:
    """Rows ``slots`` of a per-slot state leaf ``(layers, max_batch, ...)`` as
    an insert's model call takes them: what the slot holds where the row
    continues (``starts > 0``, a chunked extend), zeros where a request begins
    (whatever the slot's last tenant left behind)."""
    keep = (starts > 0).reshape(1, -1, *(1,) * (leaf.ndim - 2))
    return jnp.where(keep, leaf[:, slots], 0)


def _chosen(stats: PyTree, live: jax.Array
            ) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """The ``(layers, tokens, experts held)`` choice masks ``MoE`` sows, those
    of the tokens ``live`` calls real (any shape of ``tokens`` elements), and
    where the layer holds a share of a wider router's experts
    (``moe/layer.py``) the ``(layers, tokens)`` counts of ALL a token's picks
    (sown as ``routed``; None elsewhere), and where some of the router's
    experts cost nothing those of its picks that fell on them (``zero``; None
    elsewhere). The layers are those with experts."""
    by_name: Dict[str, list] = {"chosen": [], "routed": [], "zero": []}
    for path, leaf in jax.tree_util.tree_flatten_with_path(stats)[0]:
        name = next(k for k in by_name if f"['{k}']" in jax.tree_util.keystr(path))
        by_name[name].append(leaf)
    chosen = jnp.concatenate([c.reshape(-1, *c.shape[-2:])
                              for c in by_name["chosen"]])
    routed, zero = (jnp.concatenate([r.reshape(-1, r.shape[-1]) for r in by_name[name]])
                    if by_name[name] else None for name in ("routed", "zero"))
    return chosen & live.reshape(-1)[None, :, None], routed, zero


def _routing_sums(chosen: jax.Array, routed: Optional[jax.Array],
                  zero: Optional[jax.Array], live: jax.Array) -> jax.Array:
    """``(3,) int32`` of one model call, from :func:`_chosen`'s masks: expert
    slots touched by a real token (summed over layers), assignments of real
    tokens, layers run with a real token in them. The experts are those HELD
    and the layers those with experts, so an assignment is a pick that costs
    a product here. Where the layer holds a share of a wider router's experts
    a fourth sum follows: every pick of the real tokens, absent experts'
    included; where some of the router's experts cost nothing a fifth: the
    real tokens' picks that fell on those."""
    sums = [jnp.sum(jnp.any(chosen, axis=1)), jnp.sum(chosen),
            chosen.shape[0] * jnp.any(live)]
    for picks in (routed, zero):
        if picks is not None:
            sums.append(jnp.sum(jnp.where(live.reshape(-1)[None, :], picks, 0)))
    return jnp.stack(sums).astype(jnp.int32)


# what a configuration may add to a step's three sums, and the ``engine.stats``
# counters each of its values lands in (in this order, after the three)
WALK_SUMS = {
    "window_walk_sums": ("kv_window_slots_read", "kv_window_slots_needed"),
    "sparse_walk_sums": ("dsa_tokens_visible", "dsa_tokens_selected",
                         "dsa_latent_slots_read"),
    "stream_walk_sums": ("mhc_mix_steps",),
}


def _walk_sums(config, cache: PyTree, live: jax.Array) -> jax.Array:
    """``(3,) int32`` of one decode step: the slots of the cache the step read
    of its longest row (:class:`~neuronx_distributed_tpu.models.llama.KVWalk`,
    chunk rounding included), 1, and the slots it read summed over its rows
    (the walk's rung of rows for every chunk read); all 0 where no row is
    live. From the cache's own ``cache_index`` and the ``live`` the model is
    given, so they are the bounds the attention computed. They count the
    layers that page: a model whose window layers keep a ring a slot
    (``config.window_walk_sums``; ``models/laguna.py``) adds two, the ring
    slots those layers read and the tokens they needed; one that reads a
    chosen set of its tokens (``config.sparse_walk_sums``;
    ``models/deepseek_v32.py``) three, the tokens visible, chosen and read;
    one that carries several residual streams (``config.stream_walk_sums``;
    ``models/xing4.py``) one, its live rows times its stream mixes
    (``WALK_SUMS``)."""
    idx = next(leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
               if jax.tree_util.keystr(path).endswith("['cache_index']"))[0]
    walk = kv_walk(config, idx, live)
    sums = [walk.tokens, 1, walk.row_slots]
    for more in WALK_SUMS:
        if hasattr(config, more):
            sums.extend(getattr(config, more)(walk))
    return jnp.stack(sums).astype(jnp.int32) * jnp.any(live)


def _layout_of(leaf) -> Optional[Layout]:
    """The device layout a weight leaf HAS: an array's own; for a shape on a
    described device (which holds no array) that device's default, or the
    layout the shape was given; None where the leaf says nothing (a host
    array, a shape without a sharding)."""
    fmt = getattr(leaf, "format", None)
    if fmt is None or fmt.layout is not None or fmt.sharding is None:
        return None if fmt is None else fmt.layout
    device = min(fmt.sharding.device_set, key=lambda d: d.id)
    return Layout.from_pjrt_layout(device.client.get_default_layout(
        jnp.dtype(leaf.dtype), fmt.sharding.shard_shape(leaf.shape), device))


@contextlib.contextmanager
def _compiled_afresh():
    """Programs compiled inside are not read from JAX's persistent
    compilation cache (nor written to it). For the re-lay alone: the program
    behind ``jax.device_put(leaf, format)`` is an identity whose RESULT has
    the layout, and on the v5e (jax 0.9.0, libtpu 0.0.34) that program read
    back from the cache hands back the layout it was given: compiled it
    re-lays, loaded it does not (PERF.md section 6, PR 53). The flag is the
    process's; it is put back as it was."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _relaid(leaf, fmt: Format):
    """``leaf`` in the format ``fmt``: a copy on the device (a shape, which
    holds nothing, just says so), or an error: a weight that is not held the
    way the programs were told is refused by every one of them."""
    if isinstance(leaf, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=fmt)
    with _compiled_afresh():
        out = jax.device_put(leaf, fmt)
    got, want = out.format.layout, fmt.layout
    if got.major_to_minor != want.major_to_minor or want.tiling not in (None, got.tiling):
        raise RuntimeError(
            f"a weight {leaf.dtype}{list(leaf.shape)} asked for in {fmt.layout} came "
            f"back in {out.format.layout}")
    return out


def infer_prompt_lengths(prompt_ids: np.ndarray, pad_token_id: int = 0) -> np.ndarray:
    """Length of each right-padded prompt = 1 + rightmost non-pad position.
    Robust to ``pad_token_id`` occurring INSIDE a prompt (only the trailing
    pad run is excluded) — a plain ``(ids != pad).sum()`` is not."""
    nonpad = np.asarray(prompt_ids) != pad_token_id
    s = prompt_ids.shape[1]
    last = s - 1 - np.argmax(nonpad[:, ::-1], axis=1)   # rightmost True
    return np.where(nonpad.any(axis=1), last + 1, 0).astype(np.int32)


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (b, max_new_tokens), eos-padded
    lengths: np.ndarray         # (b,) generated lengths incl. eos


@dataclasses.dataclass
class FirstToken:
    """What an insert program samples each row's first token with
    (``CausalLM.insert(first=...)``): row ``i`` draws token index 0 of its
    request's stream, under ``fold_in(fold_in(rng, request_ids[i]), 0)``, and
    ``fold_in(rng, request_ids[i])`` becomes its slot's entry of the
    session's ``slot_keys``. Host arrays: they ride the program's call."""

    rng: jax.Array                  # the scheduler's base key
    request_ids: np.ndarray         # (rows,) uint32
    temperature: np.ndarray         # (rows,) float32
    greedy: np.ndarray              # (rows,) bool
    sampler: SlotSampler = SlotSampler()
    # (rows, vocab) bool support mask of a grammar lm's rows (None: all True)
    allowed: Optional[np.ndarray] = None


@dataclasses.dataclass
class DecodeSession:
    """Continuous-batching session: the KV cache plus host-side per-slot
    accounting (so the overflow guard travels with the session — multiple
    sessions never share counters)."""

    cache: PyTree
    lengths: np.ndarray         # (max_batch,) tokens written per slot
    active: np.ndarray          # (max_batch,) slot in use
    # paged mode: the host half of the paged pool (block tables, free-list
    # allocator, radix prefix index) — None on contiguous-slab sessions
    paged: Optional[PagedKVCache] = None
    # multi-LoRA mode (lora_rank set): the session's device-resident
    # adapter pool (inference/adapters.py) — per SESSION, like the paged
    # pool, so router replicas sharing one lm keep independent residency
    adapters: Optional[Any] = None
    # structured-decoding mode (grammar_slots set): the session's
    # device-resident grammar pool (inference/grammar.py) — per SESSION,
    # same residency economics as the adapter pool
    grammars: Optional[Any] = None
    # a model with experts: the last paged insert's routing sums, still on
    # the device (CausalLM._paged_insert_programs); None for a dense model
    insert_routing: Optional[jax.Array] = None
    # a model with per-slot state: the last paged insert's (real tokens,
    # positions) of its recurrence's scan, still on the device
    insert_scanned: Optional[jax.Array] = None
    # (max_batch,) typed keys, one request key a slot: an insert program
    # writes its rows' entries (donated in, like the cache), the fused
    # session decode samples row j's token t under fold_in(slot_keys[j], t)
    slot_keys: Optional[jax.Array] = None
    # the last insert's sampled first tokens, (rows,) int32, on the device
    first_tokens: Optional[jax.Array] = None
    # what the last insert program ran over, host numbers: the rows' real
    # tokens (a prefix hit's suffix only) and its token slots, rows x bucket
    insert_ran: Tuple[int, int] = (0, 0)
    # whether every row of that program started at position 0 (no prefix hit,
    # no earlier chunk): what latent attention's prompt form asks inside the
    # program (models/deepseek_v2.py), said here from the host's own starts
    insert_fresh: bool = True


class CausalLM:
    """Bucketed, KV-cached, continuous-batching text generation over any
    flax CLM whose config supports ``decode=True`` (LlamaForCausalLM et al).
    """

    def __init__(
        self,
        config,
        params: PyTree,
        model_cls,
        buckets: Tuple[int, ...] = (128, 512, 2048),
        max_batch: int = 4,
        param_transform=None,
        page_size: Optional[int] = None,
        page_pool_pages: Optional[int] = None,
        page_dtype: Optional[str] = None,
        prefix_cache: bool = True,
        lora_rank: Optional[int] = None,
        lora_slots: int = 0,
        lora_targets: Optional[Tuple[str, ...]] = None,
        grammar_slots: int = 0,
        grammar_states: int = 64,
        grammar_tokens: Optional[Sequence[str]] = None,
    ):
        # keep the caller's use_flash_attention: prefill buckets >= 128 run
        # the Pallas kernel with position masks (reference prefill gating,
        # attention_base.py:103-114); decode steps use the dense cached path
        self.config = dataclasses.replace(
            config, decode=True, sequence_parallel=False, remat_policy=None,
        )
        # paged KV mode: a page pool per layer (one stacked leaf) + block-table
        # sessions
        # (inference/paged_cache.py). The pool defaults to slab parity plus
        # the per-slot scratch pages; pass a smaller pool for the HBM win —
        # admission then defers under pool pressure instead of OOMing.
        self.paged = bool(page_size)
        self.prefix_cache = bool(prefix_cache)
        # cache leaves that hold ONE ROW A SLOT beside the pages (a recurrent
        # layer's state; ``models/granite_hybrid.py``). The insert programs
        # gather and scatter those rows at ``slots``; nothing else moves
        # them, so whatever reuses or moves a slot's cache BY PAGES is
        # refused here (and in ``ServeEngine``) instead of being wrong
        self.slot_rows = tuple(getattr(config, "slot_row_leaves", ()))
        self._slot_row_ends = tuple(f"['{name}']" for name in self.slot_rows)
        # False: the rows are written by a prompt from position 0 and by
        # one-token steps only (a window layer's ring), so a chunk that
        # CONTINUES a row is refused (``extend``)
        self.slot_rows_continue = bool(getattr(config, "slot_rows_continue", True))
        if self.slot_rows:
            refused = {
                "the contiguous slab (pass page_size: generate() and the slab "
                "insert scan a prompt's padding)": not page_size,
                "prefix_cache=True (a page-sharing prefix hit restores no state; "
                "pass prefix_cache=False)": bool(page_size) and prefix_cache,
                "lora_rank (the state's projections carry no adapter)": bool(lora_rank),
                f"tensor parallelism (tp = {tp_degree()}: the state leaves have no "
                "serving spec across 'tp')": tp_degree() > 1,
            }
            for what, asked in refused.items():
                if asked:
                    raise ValueError(
                        f"{type(config).__name__} keeps per-slot state "
                        f"{self.slot_rows} beside its pages and does not serve with {what}")
        if self.paged:
            if self.config.max_seq_len % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide max_seq_len "
                    f"{self.config.max_seq_len}")
            pool = page_pool_pages or (
                max_batch * (self.config.max_seq_len // page_size) + max_batch)
            over = dict(page_size=int(page_size), page_pool_pages=int(pool))
            # int8 page storage is a paged-mode knob; replace() only when
            # set so non-Llama configs without the field keep working.
            if page_dtype is not None:
                if page_dtype not in ("int8", "float32"):
                    raise ValueError(
                        f"page_dtype must be 'int8' or 'float32', "
                        f"got {page_dtype!r}")
                over["page_dtype"] = page_dtype
            self.config = dataclasses.replace(self.config, **over)
        elif page_dtype:
            raise ValueError("page_dtype requires paged mode (pass page_size)")
        # multi-LoRA serving (inference/adapters.py): the config grows the
        # pool dims so every targeted projection declares its per-slot A/B
        # stacks; each session then owns an AdapterPool whose tree rides
        # every compiled program as a read-only trailing argument (adapter
        # loads/evicts change VALUES only — zero recompiles per mix)
        self.lora = bool(lora_rank)
        if self.lora:
            slots = int(lora_slots) if lora_slots else 8
            if slots < 2:
                raise ValueError(
                    f"lora_slots must be >= 2 (slot 0 is the identity "
                    f"adapter), got {slots}")
            over = dict(lora_rank=int(lora_rank), lora_slots=slots)
            if lora_targets:
                over["lora_targets"] = tuple(lora_targets)
            self.config = dataclasses.replace(self.config, **over)
        # structured decoding (inference/grammar.py): grammar tables never
        # touch the model/config — they feed the SAMPLER inside the fused
        # session scan, so only compile_session_decode_fused grows the
        # trailing (*gr) tail (pool tables + per-slot grammar_idx / DFA
        # state / token budget). Tables are program INPUTS: grammar
        # loads/evicts change VALUES only — zero recompiles per mix.
        self.grammar = bool(grammar_slots)
        if self.grammar:
            if grammar_slots < 2:
                raise ValueError(
                    f"grammar_slots must be >= 2 (slot 0 is the identity "
                    f"grammar), got {grammar_slots}")
            if grammar_states < 2:
                raise ValueError(
                    f"grammar_states must be >= 2, got {grammar_states}")
        self.grammar_slots = int(grammar_slots)
        self.grammar_states = int(grammar_states)
        self.grammar_tokens: Optional[Tuple[str, ...]] = None
        if self.grammar:
            if grammar_tokens is None:
                from neuronx_distributed_tpu.inference.grammar import (
                    default_token_table,
                )

                grammar_tokens = default_token_table(config.vocab_size)
            if len(grammar_tokens) != config.vocab_size:
                raise ValueError(
                    f"grammar_tokens has {len(grammar_tokens)} entries for "
                    f"vocab_size {config.vocab_size}")
            self.grammar_tokens = tuple(grammar_tokens)
        self._adapter_avals_cache: Optional[PyTree] = None
        self._cache_avals_cache: Optional[PyTree] = None
        self._identity_adapters_cache: Optional[PyTree] = None
        self._identity_grammars_cache: Optional[PyTree] = None
        # the weights, HELD IN THE LAYOUT THE ONE-TOKEN STEP READS THEM IN
        # (``_compile``): as loaded until the first program is lowered
        self._params = params
        self._param_formats: Optional[PyTree] = None
        self._formats_settled = False
        self.param_relaid_leaves = 0
        self.param_relaid_bytes = 0
        self.max_batch = max_batch
        # applied INSIDE every compiled program (e.g. int8 dequantization —
        # the quantized weights are what lives in HBM and XLA fuses the
        # dequant multiply into the consuming matmuls; reference serves
        # quantized checkpoints through its QuantizedParallel layers,
        # run_llama_quantized.py)
        self.param_transform = param_transform
        self.buckets = tuple(sorted(b for b in buckets if b <= self.config.max_seq_len))
        if not self.buckets:
            raise ValueError(f"no bucket fits max_seq_len {self.config.max_seq_len}")
        self.model = model_cls(self.config)
        self._prefill = {}
        self._decode = None
        self._decode_fused = {}
        self._session_fused = {}
        self._slab_insert = {}      # (rows, bucket) -> donated slab insert
        self._paged_insert = {}     # (rows, bucket) -> donated paged insert
        self._default_first_rng: Optional[jax.Array] = None
        self._chunk_extend = {}     # (rows, bucket) -> donated chunk-prefill extend
        # observability: wall time of every AOT lower+compile, keyed by a
        # stable program signature ("session_fused_k8", "insert_r2_b128",
        # ...) — the compile half of the compile-vs-execute split (dispatch
        # latency histograms are the execute half, inference/engine.py).
        # Always recorded (one float per program, once); when a serving
        # engine attaches its tracer, each compile also lands as a span on
        # the engine "compile" lane.
        self.compile_ms: Dict[str, float] = {}
        self._compile_rows: list = []      # the compile log's row of each (compile_rows)
        self.tracer = None
        # a config with experts: the fused session decode counts what its
        # router chose (three sums, one more output; see its docstring)
        self.moe_stats = getattr(self.config, "num_experts", 0) > 1
        # three routing sums; four where the layer holds a share of what its
        # router chooses among (``moe/layer.py``: ``moe_width`` wide, experts
        # held elsewhere and experts that cost nothing included); five where
        # some cost nothing (``_routing_sums``)
        held = getattr(self.config, "num_experts", 0)
        zero = getattr(self.config, "zero_experts", 0)
        self.moe_width = (getattr(self.config, "router_experts", None) or held) + zero
        self.moe_share = self.moe_stats and self.moe_width != held
        self.moe_sums = 3 + self.moe_share + bool(zero)
        # a model that asks which tokens are real: an insert names each row's
        # suffix (the bucket's padding chooses no expert, advances no state)
        self.wants_live = self.moe_stats or bool(self.slot_rows)
        # sums a fused block's steps add up of what they read of the cache
        # (``_walk_sums``): three, and two more of a model with rings
        self.walk_sum_names = tuple(name for more, names in WALK_SUMS.items()
                                    if hasattr(self.config, more) for name in names)
        self.walk_sums = 3 + len(self.walk_sum_names)
        # per-slot state that a prompt's recurrence scans (the insert's scan sums)
        self.scans = bool(self.slot_rows) and hasattr(self.config, "scan_positions")

    # --- compilation (reference ModelBuilder.trace over CTX/TKG) ---------

    def _time_compile(self, signature: str, build):
        """Run one AOT ``lower().compile()`` under a wall timer, recording
        it per program signature, and as a row of the process's compile log
        whose parts ``_compile`` stamps. The timer is OUTSIDE the traced
        program — tracing can never perturb what XLA compiles (the
        signature-identity test pins this)."""
        with compile_log.program(signature) as row:
            t0 = time.perf_counter()
            prog = build()
            t1 = time.perf_counter()
        self.compile_ms[signature] = round((t1 - t0) * 1e3, 2)
        self._compile_rows.append(row)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.complete("compile:" + signature, ("engine", "compile"), t0, t1, args=dict(row))
        return prog

    def compile_rows(self) -> list:
        """This lm's rows of the compile log, in the order it built its
        programs: a start-up report (what each program's ``compile_ms`` went
        on: traced, lowered, compiled or loaded, hit or miss, the format pass
        apart), milliseconds rounded."""
        return [{k: round(v, 2) if isinstance(v, float) else v for k, v in row.items()}
                for row in self._compile_rows]

    # --- the layout the weights are held in ------------------------------
    # A kernel stored ``(hidden, heads, head_dim)`` lies, by default, tiled
    # over ``(heads, head_dim)``; the one-token step's dot wants ``hidden`` in
    # the tile, so a program that assumes the default copies the whole
    # stacked leaf at its entry, every call (768 MiB a block at Mistral-7B's
    # widths), and an insert copies a layer's slice in its layer scan. So the
    # FIRST program lowered settles the formats. One that runs the one-token
    # step (``decode``, which ``compile()`` lowers before anything else; a
    # fused block where a caller builds that first) is compiled once with
    # ``Layout.AUTO`` on its ``params`` argument, to ASK: what the compiler
    # reports is the format tree, the few leaves that lie otherwise are
    # re-laid once, and every program is lowered with those formats fixed on
    # its ``params`` argument. Nothing names a leaf. On a backend whose
    # compiler keeps every default (the CPU) the asking compile IS the
    # program, nothing moves and everything is lowered as it always was.

    @property
    def params(self) -> PyTree:
        return self._params

    @params.setter
    def params(self, tree: PyTree) -> None:
        """New weights take the formats the compiled programs were given
        (those programs refuse a committed leaf in any other)."""
        self._params = tree
        if self._param_formats is not None:
            self._hold(self._param_formats)

    def _hold(self, formats: PyTree) -> None:
        """Settle the weights' formats as ``formats`` (a ``Format`` a leaf,
        or None: as the leaf is) and re-lay the leaves that are held in
        another layout, one at a time: this tree lets go of a leaf before the
        next is copied, so the most held twice is one leaf (a caller that
        keeps the tree it handed in keeps those leaves too)."""
        flat, tree = jax.tree_util.tree_flatten(self._params)
        want = tree.flatten_up_to(formats)
        self._params = None
        with compile_log.stretch("relay_ms"):
            for i, fmt in enumerate(want):
                have = _layout_of(flat[i])
                if fmt is None or have is None or have == fmt.layout:
                    want[i] = None
                    continue
                flat[i] = _relaid(flat[i], fmt)
                self.param_relaid_leaves += 1
                self.param_relaid_bytes += (
                    int(np.prod(flat[i].shape)) * jnp.dtype(flat[i].dtype).itemsize)
        self._params = tree.unflatten(flat)
        self._param_formats = (tree.unflatten(want)
                               if any(f is not None for f in want) else None)
        self._formats_settled = True

    def _ask_formats(self, fn, rest, jit_kw):
        """The format pass: ``fn`` compiled with its ``params`` argument as
        shapes under ``Layout.AUTO`` (each leaf's own sharding kept), the
        formats that compile reports held (``_hold``). Returns the compiled
        program where it is the one a plain lowering gives: no leaf moved,
        and every other argument and every result in the default, row-major
        order. The TPU compiler, once ANY argument is its to lay out, lays
        out the others too (a latent pool, a ``(rows, 1)`` token column, the
        logits), and the next program would refuse those: there the pass is
        kept for its answer alone and the caller compiles again, formats
        fixed. Returns None then, and what the asking compile took is set
        aside as the row's ``asking_ms`` (``compile_log``); a program that is
        kept took the row's ordinary parts."""
        leaves, tree = jax.tree_util.tree_flatten(self._params)
        shapes = [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype) for leaf in leaves]
        auto = [Format(Layout.AUTO, getattr(leaf, "sharding", None)
                       if getattr(leaf, "committed", True) else None)
                for leaf in leaves]
        asked = compile_log.staged(
            jax.jit(fn, in_shardings=(tree.unflatten(auto), *(None,) * len(rest)), **jit_kw),
            tree.unflatten(shapes), *rest)
        formats, *others = asked.input_formats[0]
        self._hold(formats)
        plain = self._param_formats is None and all(
            f.layout is None
            or f.layout.major_to_minor == tuple(range(len(f.layout.major_to_minor)))
            for f in jax.tree_util.tree_leaves((others, asked.output_formats)))
        if plain:
            return asked
        compile_log.set_aside("asking_ms")
        return None

    def _compile(self, fn, *rest, chooses: bool = False, **jit_kw):
        """``jax.jit(fn, **jit_kw).lower(self.params, *rest).compile()``, the
        one place a program meets the weights, its stages stamped on the
        compile log's open row (``compile_log.staged``). The first call
        settles their formats: where the program ``chooses`` (it runs the
        one-token step) by asking (``_ask_formats``), otherwise as the weights
        are. Every program takes them fixed."""
        if not self._formats_settled and chooses:
            asked = self._ask_formats(fn, rest, jit_kw)
            if asked is not None:
                return asked
        self._formats_settled = True
        if self._param_formats is not None:
            jit_kw["in_shardings"] = (self._param_formats, *(None,) * len(rest))
        return compile_log.staged(jax.jit(fn, **jit_kw), self._params, *rest)

    def relaid_leaves(self) -> list:
        """``[(path, leaf)]`` of the weight leaves held off the layout they
        were loaded in (``scripts/big_ops.py --formats`` and the tests list
        them)."""
        if self._param_formats is None:
            return []
        formats = jax.tree_util.tree_leaves(self._param_formats, is_leaf=lambda f: f is None)
        return [(jax.tree_util.keystr(path), leaf) for (path, leaf), fmt in zip(
            jax.tree_util.tree_flatten_with_path(self._params)[0], formats) if fmt is not None]

    def _resolve(self, params):
        """The single place the serving param transform applies (e.g. int8
        dequantization) — every compiled program must route through it."""
        return self.param_transform(params) if self.param_transform else params

    # --- multi-LoRA plumbing ---------------------------------------------
    # Adapter-enabled programs take TWO trailing args — (adapters tree,
    # per-row adapter_idx) — threaded as ``*ad`` so every builder and call
    # site below stays byte-identical when lora is off. The tree is the
    # session pool's device arrays (values change on load/evict, shapes
    # never), the idx a tiny int32 vector the program substitutes into the
    # tree's adapter_idx leaves at its own batch width.

    def _adapter_avals(self) -> Optional[PyTree]:
        """Abstract ``"adapters"`` collection at session width — the ONE
        canonical aval every adapter-enabled program lowers against (pinned
        to the serving specs under a mesh, like the cache avals: A fan-in
        sharded for row-parallel targets, B fan-out sharded for
        column-parallel ones)."""
        if not self.lora:
            return None
        if self._adapter_avals_cache is None:
            ids0 = jnp.zeros((self.max_batch, self.buckets[0]), jnp.int32)

            def shape_fn(params, ids):
                _, mut = self.model.apply(
                    {"params": self._resolve(params)}, ids,
                    mutable=["cache", "adapters"])
                return mut["adapters"]

            avals = jax.eval_shape(shape_fn, self.params, ids0)
            self._adapter_avals_cache = shard_avals(avals)
        return self._adapter_avals_cache

    def new_adapter_pool(self):
        """Fresh device-resident adapter pool (slot 0 = identity) sized by
        the config's (lora_slots, lora_rank) — one per session."""
        from neuronx_distributed_tpu.inference.adapters import AdapterPool

        if not self.lora:
            raise ValueError("CausalLM was built without lora_rank")
        return AdapterPool(self._adapter_avals(), self.config.lora_rank,
                           self.config.lora_slots)

    def _identity_adapters(self) -> PyTree:
        """All-zeros pool (every row the identity adapter) — what
        session-less paths like :meth:`generate` feed adapter-enabled
        programs; the correction is exactly zero."""
        if self._identity_adapters_cache is None:
            self._identity_adapters_cache = zeros_like_avals(
                self._adapter_avals())
        return self._identity_adapters_cache

    def _with_adapter_idx(self, tree: PyTree, idx: jax.Array) -> PyTree:
        """Inside-jit substitution of the per-row adapter indices into every
        (layer-stacked) adapter_idx leaf at the program's batch width — the
        one session tree serves programs of every row count."""
        def fix(path, leaf):
            if jax.tree_util.keystr(path).endswith("['adapter_idx']"):
                return jnp.broadcast_to(idx.astype(leaf.dtype)[None, :],
                                        (leaf.shape[0], idx.shape[0]))
            return leaf

        return jax.tree_util.tree_map_with_path(fix, tree)

    def _ad_vars(self, params, cache, ad) -> dict:
        """The apply-variables dict shared by every program body: params
        (+transform), optional cache, and — when the ``*ad`` tail is
        present — the adapters collection with row-width indices."""
        d = {"params": self._resolve(params)}
        if cache is not None:
            d["cache"] = cache
        if ad:
            adapters, aidx = ad
            d["adapters"] = self._with_adapter_idx(adapters, aidx)
        return d

    def _ad_lower(self, rows: int) -> tuple:
        """Trailing lowering avals for adapter-enabled programs: the
        canonical pool avals plus a (rows,) idx — () when lora is off."""
        if not self.lora:
            return ()
        return (self._adapter_avals(),
                repl_avals(jax.ShapeDtypeStruct((rows,), jnp.int32)))

    def _ad_args(self, pool, idx) -> tuple:
        """Trailing call args: the pool's live tree (identity zeros when no
        pool rides along) + the per-row slot indices — () when lora is
        off."""
        if not self.lora:
            return ()
        tree = pool.tree if pool is not None else self._identity_adapters()
        return (tree, np.asarray(idx, np.int32))

    # --- structured-decoding plumbing ------------------------------------
    # Grammar-enabled session programs take a trailing ``*gr`` quad —
    # (tables tree, grammar_idx (b,), dfa_state (b,), token_budget (b,)) —
    # threaded like the ``*ad`` pair so every builder/call site stays
    # byte-identical when grammars are off. Only the fused session scan
    # consumes it: enforcement is a per-step mask on the SAMPLER, never a
    # model change. The first-token sample (insert/chunk-finish/replay) and
    # the stepwise oracle apply the same mask host-side via the engine.

    def new_grammar_pool(self):
        """Fresh device-resident grammar pool (slot 0 = accept-everything
        identity) sized by (grammar_slots, grammar_states) over this lm's
        token table — one per session."""
        from neuronx_distributed_tpu.inference.grammar import GrammarPool

        if not self.grammar:
            raise ValueError("CausalLM was built without grammar_slots")
        return GrammarPool(self.grammar_slots, self.grammar_states,
                           self.grammar_tokens)

    def _identity_grammars(self) -> Dict[str, jax.Array]:
        """All-identity table stack (every row unconstrained) — what
        pool-less dispatches feed grammar-enabled programs."""
        if self._identity_grammars_cache is None:
            from neuronx_distributed_tpu.inference.grammar import _INF

            G, S = self.grammar_slots, self.grammar_states
            V = self.config.vocab_size
            # eager shard_out: born vocab-sharded under a TP mesh, so the
            # AOT grammar-tailed programs never reshard the identity tables
            self._identity_grammars_cache = shard_out({
                "need": jnp.concatenate(
                    [jnp.zeros((1, S, V), jnp.int32),
                     jnp.full((G - 1, S, V), _INF, jnp.int32)]),
                "next": jnp.zeros((G, S, V), jnp.int32),
                "terminal": jnp.zeros((G, S), bool),
            })
        return self._identity_grammars_cache

    def _gr_lower(self, rows: int) -> tuple:
        """Trailing lowering avals for grammar-enabled session programs:
        the table-stack avals plus (rows,) idx/state/budget vectors — ()
        when grammars are off."""
        if not self.grammar:
            return ()
        G, S = self.grammar_slots, self.grammar_states
        V = self.config.vocab_size
        tree = shard_avals({
            "need": jax.ShapeDtypeStruct((G, S, V), jnp.int32),
            "next": jax.ShapeDtypeStruct((G, S, V), jnp.int32),
            "terminal": jax.ShapeDtypeStruct((G, S), jnp.bool_),
        })
        return (tree,
                *repl_avals((jax.ShapeDtypeStruct((rows,), jnp.int32),
                             jax.ShapeDtypeStruct((rows,), jnp.int32),
                             jax.ShapeDtypeStruct((rows,), jnp.int32))))

    def _gr_args(self, pool, gidx, gstate, gbudget) -> tuple:
        """Trailing call args: the pool's live tables (identity when no
        pool rides along) + per-row grammar slot / DFA state / budget — ()
        when grammars are off. Host rows ride the call as host arrays; a
        device value (the async loop chains the DFA state) passes through."""
        if not self.grammar:
            return ()
        tree = pool.tree if pool is not None else self._identity_grammars()
        return (tree, *(v if isinstance(v, jax.Array)
                        else np.asarray(v, np.int32)
                        for v in (gidx, gstate, gbudget)))

    @staticmethod
    def grammar_allowed(tree, gidx, gstate, gbudget, counts):
        """The (b, vocab) budget-aware allowed mask — THE structured-
        decoding enforcement boolean, used identically by the fused scan
        (device tables, inside the program) and the engine's host-side
        sampling sites (first token, stepwise oracle). ``need[s, v]`` is
        the budget a transition still requires after taking it (INF =
        forbidden), so the mask is ONE row gather plus two compares:
        ``need ≤ budget − counts − 1``, falling back to the plain
        reachability mask (``need < INF``) when the budget-aware set
        empties (only frozen rows), with identity rows (grammar_idx 0)
        all-True via slot 0's all-zeros need."""
        need = tree["need"][gidx, gstate]                 # (b, V)
        remaining = (gbudget - counts - 1)[:, None]
        ok = need <= remaining
        fb = need < jnp.int32(2 ** 30)
        return jnp.where(ok.any(axis=-1, keepdims=True), ok, fb)

    def compile(self) -> "CausalLM":
        # every cache a program RETURNS is pinned to the serving specs
        # (_shard_out, no-op off-mesh): session caches round-trip between
        # AOT programs whose cache inputs are lowered on the SAME specs
        # (_cache_avals) — an unconstrained output lets GSPMD pick a layout
        # the next call then rejects (the PR 3 class: batch-over-'edp'
        # whenever max_batch divides it; trace-shape-dependent, so it bit
        # only some schedules). Under a TP mesh the specs shard KV heads /
        # adapter fan-in-out / grammar vocab (inference/partition.py);
        # off-mesh or at tp=1 they degrade to the replicated pin.
        def prefill_fn(params, ids, *ad):
            logits, mut = self.model.apply(self._ad_vars(params, None, ad),
                                           ids, mutable=["cache"])
            return logits, self._shard_out(mut["cache"])

        def decode_fn(params, cache, ids, *ad):
            # a model with per-slot state is told its live rows first
            # (``_live_args``): a row that is not live keeps its state
            live, ad = (ad[:1], ad[1:]) if self.slot_rows else ((), ad)
            logits, mut = self.model.apply(self._ad_vars(params, cache, ad),
                                           ids, *live, mutable=["cache"])
            return logits, self._shard_out(mut["cache"])

        ad0 = self._ad_lower(self.max_batch)
        # decode FIRST: it runs the one-token step, so it is the program that
        # says in which layout the weights are held (``_compile``), and
        # everything lowered after it takes them so. Donate the cache
        # (argnum 1). Abstract cache avals suffice for lowering — no need to
        # execute a real prefill at startup (_cache_avals also pins them
        # replicated under a mesh).
        cache0 = self._cache_avals()
        tok = jnp.zeros((self.max_batch, 1), jnp.int32)
        self._decode = self._time_compile(
            "decode",
            lambda: self._compile(
                decode_fn, cache0, tok,
                *self._live_args(np.ones((self.max_batch,), bool)), *ad0,
                chooses=True, donate_argnums=(1,)))
        if not self.paged:
            # paged mode never runs the stand-alone prefill (its cache init
            # would alias every slot onto page 0): all prefill goes through
            # the pool-donating insert programs, compiled lazily per width
            for bucket in self.buckets:
                ids = jnp.zeros((self.max_batch, bucket), jnp.int32)
                self._prefill[bucket] = self._time_compile(
                    f"prefill_b{bucket}",
                    lambda ids=ids: self._compile(prefill_fn, ids, *ad0))
        return self

    def _live_args(self, live) -> tuple:
        """What the one-step decode program takes after its tokens: for a
        model with per-slot state the ``(max_batch, 1)`` bool of the rows the
        step advances, for every other model nothing."""
        if not self.slot_rows:
            return ()
        return repl_args(jnp.asarray(np.asarray(live, bool).reshape(-1, 1)))

    def compile_decode_fused(self, steps: int, sampler: Optional[Sampler] = None,
                             eos_token_id: Optional[int] = None,
                             pad_token_id: int = 0):
        """Compile ``steps`` decode iterations as ONE device program
        (``lax.scan`` over the single-token step; the donated cache is the
        scan's carry, and its K/V leaves the carry of the model's layer scan
        inside it: one buffer, updated in place, argument to result).

        Rationale: step decode pays one program dispatch per token; at small
        per-layer cost that fixed dispatch dominates (the ~5 ms/token decode
        intercept attributed in PROFILE.md's r5 study). Fusing K steps
        amortizes it K-fold. Any :class:`Sampler` works — the scan body
        carries an rng key and splits once per step (the SAME fold-in order
        as the stepwise path, so greedy and sampled outputs are
        token-identical to step decode). Per-token EOS is handled inside the
        scan: the emitted token at position i is frozen to ``pad_token_id``
        for rows already done BEFORE step i, and ``done`` latches on the eos
        token — the device may still compute (never emit) tokens past a
        row's EOS, exactly like the step path keeps decoding finished rows
        until the whole batch is done. The param transform (e.g. int8
        dequant) is applied INSIDE the scan body — quantized weights stay in
        HBM and XLA fuses the dequant into each step's matmuls, exactly like
        the single-step program.

        Returns the compiled program ``(params, cache, tok (b,1), rng,
        done (b,)) -> (tokens (steps, b), cache, next_tok, rng, done)`` where
        ``tokens[i]`` is the (EOS-masked) token emitted at iteration ``i``
        and ``next_tok``/``rng``/``done`` feed a follow-up call. Cached per
        ``(steps, sampler, eos, pad)``.

        Reference counterpart: the token-generation submodel of the CTX/TKG
        split (examples/inference/modules/model_base.py) — one traced
        program per generated token; the fused loop is the TPU-native
        improvement XLA's static control flow makes free.
        """
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        sampler = sampler or Sampler(greedy=True)
        key = (steps, sampler, eos_token_id, pad_token_id)
        if key in self._decode_fused:
            return self._decode_fused[key]

        def fused_fn(params, cache, tok, rng, done, *ad):
            def body(carry, _):
                cache, tok, rng, done = carry
                rng, sub = jax.random.split(rng)
                logits, mut = self.model.apply(
                    self._ad_vars(params, cache, ad), tok, mutable=["cache"]
                )
                nxt = sampler(logits[:, 0, :], sub)
                # emission masked by done-BEFORE-this-step (the stepwise
                # record() order); the raw token still feeds the next step,
                # also matching stepwise
                out = jnp.where(done, jnp.int32(pad_token_id), nxt)
                if eos_token_id is not None:
                    done = done | (nxt == eos_token_id)
                return (mut["cache"], nxt[:, None], rng, done), out

            (cache, tok, rng, done), toks = jax.lax.scan(
                body, (cache, tok, rng, done), None, length=steps)
            return toks, self._shard_out(cache), tok, rng, done

        cache0 = self._cache_avals()
        tok0 = jnp.zeros((self.max_batch, 1), jnp.int32)
        done0 = jnp.zeros((self.max_batch,), bool)
        self._decode_fused[key] = self._time_compile(
            f"decode_fused_k{steps}",
            lambda: self._compile(
                fused_fn, cache0, tok0, jax.random.key(0), done0,
                *self._ad_lower(self.max_batch),
                chooses=True, donate_argnums=(1,)))
        return self._decode_fused[key]

    def _cache_avals(self) -> PyTree:
        """Abstract KV-cache structure at session width (max_batch) — enough
        to lower cache-carrying programs without executing a prefill. When a
        device mesh is active the avals are PINNED to the serving specs
        (tp-sharded KV heads, replicated control leaves): left unannotated,
        GSPMD may assign the compiled program arbitrary cache input layouts
        (observed: batch over 'edp' whenever max_batch divides it), which
        then reject the session cache at call time. Computed once: it traces
        the whole model, and every program's lowering and every session asks
        for it (a serving cell builds 8 to 16 insert programs)."""
        if self._cache_avals_cache is not None:
            return self._cache_avals_cache
        ids0 = jnp.zeros((self.max_batch, self.buckets[0]), jnp.int32)

        def prefill_shape(params, ids):
            # lora lms must let the adapters collection INIT here (it is
            # not provided): mutable and discarded — shapes only
            mutable = ["cache", "adapters"] if self.lora else ["cache"]
            _, mut = self.model.apply({"params": self._resolve(params)}, ids,
                                      mutable=mutable)
            return mut["cache"]

        avals = jax.eval_shape(prefill_shape, self.params, ids0)
        self._cache_avals_cache = shard_avals(avals)
        return self._cache_avals_cache

    # the rows of the fused session decode's ``rows`` argument, in order
    BLOCK_ROWS = ("counts", "lengths", "active", "eos_ids", "temperature",
                  "greedy")

    @staticmethod
    def block_rows(counts, lengths, active, eos_ids, temperature, greedy,
                   out: Optional[np.ndarray] = None) -> np.ndarray:
        """The six row arrays of a fused block that only the host writes, as
        the ONE ``(6, rows)`` int32 host matrix the program takes
        (``BLOCK_ROWS`` is the order): ``temperature``'s float32 bits, the
        bools as 0/1. NumPy only, no device op. Filled into ``out`` where
        given (the sync loop's kept buffer), else into a new matrix (a copy
        of the mirrors, which the async loop needs)."""
        if out is None:
            out = np.empty((len(CausalLM.BLOCK_ROWS), len(counts)), np.int32)
        bits = np.asarray(temperature, np.float32).view(np.int32)
        for i, row in enumerate((counts, lengths, active, eos_ids, bits,
                                 greedy)):
            out[i] = row
        return out

    @staticmethod
    def unpack_block_rows(rows: jax.Array) -> tuple:
        """:meth:`block_rows` undone inside a program: every row in the
        dtype and with the bits it had on the host."""
        counts, lengths, active, eos_ids, bits, greedy = rows
        return (counts, lengths, active != 0, eos_ids,
                jax.lax.bitcast_convert_type(bits, jnp.float32), greedy != 0)

    def compile_session_decode_fused(self, steps: int,
                                     slot_sampler: Optional[SlotSampler] = None,
                                     pad_token_id: int = 0):
        """Compile ``steps`` continuous-batching decode iterations as ONE
        device program — the session counterpart of
        :meth:`compile_decode_fused`, with the per-slot serving state carried
        ON-DEVICE so the whole slot pool advances K tokens per dispatch.

        The scan body carries ``(cache, tok, counts, lengths, done)`` (the
        cache donated and aliased to the result, as in
        :meth:`compile_decode_fused`) and closes over the block-invariant
        ``slot_keys``/``active``/``eos_ids``/``temperature``/``greedy`` row arrays (membership and per-request
        samplers change only at block boundaries, where the scheduler passes
        refreshed arrays — they ride the dispatch, costing no extra host op):

        * per-REQUEST rng: each slot carries its request's key
          (``fold_in(engine base, request_id)``, a ``(b,)`` typed key array)
          and a per-slot generated-token counter; step i samples row j under
          ``fold_in(slot_keys[j], counts[j])`` via the per-row branch of
          :class:`SlotSampler`. A request's t-th token therefore draws from
          ``fold_in(request_key, t)`` REGARDLESS of schedule — what makes
          chunked-prefill admission (which shifts every subsequent block)
          bit-identical to one-shot admission even for sampled requests;
        * emission: the token emitted at step i is frozen to ``pad_token_id``
          for rows that were done OR inactive BEFORE step i (the stepwise
          engine's record order); the raw sample still feeds step i+1,
          matching step decode exactly;
        * per-token EOS: ``done`` latches when an active row samples its own
          ``eos_ids`` entry (−1 disables — per-REQUEST eos ids ride a device
          array instead of forcing a recompile per id mix);
        * overflow guard: an active row whose next write would run past
          ``max_seq_len`` latches ``done`` — its later emissions pad and the
          (dropped) cache writes can never wrap onto a neighbour. The
          scheduler prevents this at admission; the latch makes the device
          program safe even against a buggy/hostile driver.

        Every latch is a pure function of the EMITTED tokens and the block
        inputs, so a host scheduler can mirror ``lengths``/``done``/
        ``counts`` exactly from the single per-block fetch — one program
        call + one fetch per K tokens for the whole pool.

        Structured decoding (lm built with ``grammar_slots``): the program
        grows a trailing ``(grammar tables, grammar_idx (b,), dfa_state
        (b,), token_budget (b,))`` quad. Each step gathers the current
        state's allowed-mask/next-state rows (budget-aware — see
        :meth:`grammar_allowed`), the sampler floors disallowed logits to
        −1e30 before greedy/categorical selection, the emitted token drives
        a next-state gather carried through the scan, and entering an
        accept-terminal state latches ``done`` exactly like EOS. Identity
        rows (idx 0) see an all-ones mask — their logits are bit-for-bit
        untouched — and the tables ride the dispatch as inputs: zero extra
        host ops, zero recompiles when the grammar mix changes.

        Returns the compiled program ``(params, cache, tok (b,1), slot_keys
        (b,) keys, done (b,), rows (6, b) int32[, *ad][, *gr]) -> (tokens
        (steps, b), cache, next_tok, lengths, done[, dfa_state], walked[,
        routing])``. ``rows`` is :meth:`block_rows` of the six row arrays
        that only the host writes (``counts``, ``lengths``, ``active``,
        ``eos_ids``, ``temperature``, ``greedy``): one host matrix, so one
        transfer inside the call; ``tok`` and ``done`` stay arguments of
        their own because the async loop chains them from the block before
        without a fetch. The
        trailing ``dfa_state`` rides out only for grammar-enabled lms: the
        async double-buffered loop feeds block t+1's grammar quad from
        block t's OUTPUT without a host fetch, so the final carried state
        must surface as a device value (the sync path ignores it). Cached
        per ``(steps, slot_sampler, pad)``.

        Every model is told which rows are live at each step (``live``:
        active and not done): they alone set how far the step reads the cache
        and of how many rows (``models/llama.py::KVWalk``). After the row
        outputs (and the ``dfa_state``) comes ``(3,) int32``: the slots of the
        cache the block's steps read of their longest row, summed over the
        steps with a live row, the number of those steps, and the slots read
        summed over the rows of each step's rung (``_walk_sums``). Over
        ``steps x max_seq_len`` the first is the share of the logical slab a
        step read; the third over the first x ``max_batch`` is the share of
        that rectangle's rows.

        A model with experts (``self.moe_stats``) returns one more value,
        LAST: ``(3,) int32`` sums over the block's steps and the layers of
        what the router chose for the rows that were live at each step —
        expert slots touched (experts with a live row), assignments (live
        rows x top-k x layers) and layer steps with a live row. Dead rows
        choose no expert, so ``touched / (layer_steps x experts)`` is the
        share of the expert weights the block read.
        """
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        slot_sampler = slot_sampler or SlotSampler()
        key = (steps, slot_sampler, pad_token_id)
        if key in self._session_fused:
            return self._session_fused[key]
        max_len = self.config.max_seq_len
        n_ad = 2 if self.lora else 0
        moe = self.moe_stats

        def fused_fn(params, cache, tok, slot_keys, done, rows, *tail):
            (counts, lengths, active, eos_ids, temperature,
             greedy) = self.unpack_block_rows(rows)
            ad = tail[:n_ad]
            gr = tail[n_ad:]
            if gr:
                gtree, gidx, gstate0, gbudget = gr
                gactive = gidx > 0

            def body(carry, _):
                if moe:
                    *carry, mstats = carry
                *carry, walked = carry
                if gr:
                    cache, tok, counts, lengths, done, gstate = carry
                else:
                    cache, tok, counts, lengths, done = carry
                with jax.named_scope("sampler"):
                    sub = jax.vmap(jax.random.fold_in)(slot_keys, counts)
                live = active & ~done
                with jax.named_scope("bookkeeping"):
                    walked = walked + _walk_sums(self.config, cache, live)
                logits, mut = self.model.apply(
                    self._ad_vars(params, cache, ad), tok,
                    # dead rows choose no expert (moe/layer.py), do not set
                    # how far the step reads the cache and are not read (KVWalk)
                    live=live[:, None],
                    mutable=["cache", "moe_stats"] if moe else ["cache"]
                )
                with jax.named_scope("sampler"):
                    allowed = None
                    if gr:
                        allowed = self.grammar_allowed(
                            gtree, gidx, gstate, gbudget, counts)
                    nxt = slot_sampler(logits[:, 0, :], sub, temperature,
                                       greedy, allowed=allowed)
                with jax.named_scope("bookkeeping"):
                    done_before = done
                    if moe:
                        mstats = mstats + _routing_sums(
                            *_chosen(mut["moe_stats"], live), live)
                    out = jnp.where(done | ~active, jnp.int32(pad_token_id),
                                    nxt)
                    done = done | (active & (eos_ids >= 0) & (nxt == eos_ids))
                    if gr:
                        # frozen rows keep their state; live grammar rows
                        # step to next[state, emitted] and latch done on an
                        # accept-terminal landing (the grammar's EOS)
                        adv = gactive & active & ~done_before
                        new_state = gtree["next"][gidx, gstate, nxt]
                        gstate = jnp.where(adv, new_state, gstate)
                        done = done | (adv & gtree["terminal"][gidx, gstate])
                    counts = counts + 1
                    lengths = lengths + 1
                    done = done | (active & (lengths + 1 >= max_len))
                return (mut["cache"], nxt[:, None], counts, lengths, done,
                        *((gstate,) if gr else ()), walked,
                        *((mstats,) if moe else ())), out

            init = ((cache, tok, counts, lengths, done, gstate0) if gr
                    else (cache, tok, counts, lengths, done))
            init = (*init, jnp.zeros((self.walk_sums,), jnp.int32))
            if moe:
                init = (*init, jnp.zeros((self.moe_sums,), jnp.int32))
            carry, toks = jax.lax.scan(body, init, None, length=steps)
            cache, tok, _counts, lengths, done = carry[:5]
            last = self._replicate_out(carry[-2:] if moe else carry[-1:])
            # row outputs pinned replicated: the async loop feeds block
            # t+1's inputs from these COMMITTED values (and edits them with
            # eager staged-override ops), so they must come back in exactly
            # the layout the lowered row inputs require — see repl_args
            if gr:
                return (*self._replicate_out((toks,)), self._shard_out(cache),
                        *self._replicate_out((tok, lengths, done, carry[5])),
                        *last)
            return (*self._replicate_out((toks,)), self._shard_out(cache),
                    *self._replicate_out((tok, lengths, done)), *last)

        b = self.max_batch
        self._session_fused[key] = self._time_compile(
            f"session_fused_k{steps}",
            lambda: self._compile(
                fused_fn, self._cache_avals(),
                *repl_args(jnp.zeros((b, 1), jnp.int32),
                           jax.random.split(jax.random.key(0), b),
                           jnp.zeros((b,), bool),
                           jnp.zeros((len(self.BLOCK_ROWS), b), jnp.int32)),
                *self._ad_lower(b), *self._gr_lower(b),
                chooses=True, donate_argnums=(1,)))
        return self._session_fused[key]

    def _bucket_for(self, s: int) -> int:
        for b in self.buckets:
            if s <= b:
                return b
        raise ValueError(f"prompt length {s} exceeds largest bucket {self.buckets[-1]}")

    def _slot_row_leaves(self, tree: PyTree):
        """``(path, leaf)`` of the per-slot state leaves of a cache tree."""
        ends = self._slot_row_ends
        return [(p, leaf) for p, leaf in
                ((jax.tree_util.keystr(path), leaf) for path, leaf in
                 jax.tree_util.tree_flatten_with_path(tree)[0])
                if ends and p.endswith(ends)]

    def kv_cache_bytes(self) -> dict:
        """KV-cache footprint of this serving config. ``kv_bytes`` is what
        a session allocates PER CHIP — the HBM-sizing number: under a TP
        mesh the KV pools shard their head axis, so each shard holds
        ``1/tp`` of every sharded leaf (replicated off-mesh / at tp=1 /
        non-divisible heads: per-chip == global; a model's per-slot state is
        not in it but under ``state_bytes``). ``kv_bytes_global`` is
        the full logical footprint (the host-width number: handoff
        payloads and host-tier pages gather to full width);
        ``kv_slab_bytes`` is the per-chip slab-equivalent for the same
        dims — the memory-sizing formula the README documents (paged/slab
        = page_pool_pages*page_size / (max_batch*max_seq_len)).

        Dtype-aware: every count is derived from each leaf's OWN dtype,
        so ``page_dtype="int8"`` pools report ~1/4 the fp32 bytes (plus
        the fp32 scale leaves, which are counted in actual/global but
        contribute nothing to the slab equivalent — the slab baseline is
        always the un-quantized ``config.dtype`` slab, which is what the
        int8 pool is competing against for HBM)."""
        from neuronx_distributed_tpu.parallel import mesh as ps

        tp = (ps.get_tensor_model_parallel_size()
              if ps.model_parallel_is_initialized() else 1)
        pool_leaves = leaf_paths(KV_PAGE_LEAVES)
        scale_leaves = leaf_paths(KV_SCALE_LEAVES)
        slab_itemsize = jnp.dtype(self.config.dtype).itemsize
        actual = actual_global = slab = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._cache_avals())[0]:
            p = jax.tree_util.keystr(path)
            is_pool = p.endswith(pool_leaves)
            if not (is_pool or p.endswith(scale_leaves)):
                continue
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            spec = leaf_partition_spec(p, leaf.shape, tp)
            shard_div = tp if any(ax is not None for ax in spec) else 1
            actual += nbytes // shard_div
            actual_global += nbytes
            if not is_pool:
                continue  # scale leaves have no slab counterpart
            if self.paged:
                tokens = self.config.page_pool_pages * self.config.page_size
                slab_nbytes = (int(np.prod(leaf.shape)) * slab_itemsize
                               // shard_div)
                slab += slab_nbytes * (
                    self.max_batch * self.config.max_seq_len) // tokens
            else:
                slab += nbytes // shard_div
        out = {"kv_bytes": actual, "kv_bytes_global": actual_global,
               "kv_slab_bytes": slab}
        if self.slot_rows:
            # counted apart from the pages: it follows max_batch, not tokens
            # (a window layer's rings under their own name)
            out["window_bytes" if hasattr(self.config, "window_walk_sums")
                else "state_bytes"] = sum(
                int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for _, leaf in self._slot_row_leaves(self._cache_avals()))
        return out

    def kv_page_bytes(self) -> int:
        """Bytes ONE physical KV page occupies across every layer ON ONE
        CHIP — the HBM-pool sizing unit (per-shard under a TP mesh: page
        capacity per chip-equivalent multiplies by tp). Paged mode only."""
        if not self.paged:
            raise ValueError("kv_page_bytes applies to paged mode only")
        return self.kv_cache_bytes()["kv_bytes"] // self.config.page_pool_pages

    def kv_page_bytes_host(self) -> int:
        """Bytes one LOGICAL page occupies at full width — the host-tier /
        handoff sizing unit (``--host_tier_bytes / kv_page_bytes_host()``
        = tier capacity in pages): page reads gather every shard's slice,
        so host copies are always full-width regardless of TP degree."""
        if not self.paged:
            raise ValueError("kv_page_bytes_host applies to paged mode only")
        return (self.kv_cache_bytes()["kv_bytes_global"]
                // self.config.page_pool_pages)

    # --- continuous batching (slot-level session API) --------------------
    # The reference reorders sequences into KV-cache slots via its seq_ids
    # machinery (model_wrapper.py:207); here the session object carries the
    # cache plus HOST-side per-slot length accounting, and slots are batch
    # rows: `insert` prefills CHOSEN rows while the other rows' cache
    # entries are untouched mid-generation.

    def start_session(self) -> "DecodeSession":
        """Fresh decode session (all slots free). Sessions are independent —
        accounting travels WITH the session, so multiple concurrent sessions
        keep their own overflow guards."""
        if self._decode is None:
            self.compile()
        cache = self._cache_avals()
        session = DecodeSession(
            # born with the serving shardings: the AOT programs were
            # lowered on these avals and reject a drifted layout
            cache=zeros_like_avals(cache),
            lengths=np.zeros((self.max_batch,), np.int64),
            active=np.zeros((self.max_batch,), bool),
            slot_keys=repl_args(
                jax.random.split(jax.random.key(0), self.max_batch))[0],
        )
        if self.paged:
            session.paged = PagedKVCache(
                self.config.page_size, self.config.page_pool_pages,
                self.max_batch, self.config.max_seq_len,
                prefix_cache=self.prefix_cache)
            session.cache = _set_block_tables(session.cache,
                                              session.paged.tables)
        if self.lora:
            session.adapters = self.new_adapter_pool()
        if self.grammar:
            session.grammars = self.new_grammar_pool()
        return session

    def _check_slots(self, slot_ids: np.ndarray) -> None:
        if len(slot_ids) == 0:
            raise ValueError("empty slot_ids")
        if len(np.unique(slot_ids)) != len(slot_ids):
            raise ValueError(f"duplicate slot ids {slot_ids.tolist()}")
        if (slot_ids < 0).any() or (slot_ids >= self.max_batch).any():
            # negative ids would wrap via numpy indexing and clobber a live slot
            raise ValueError(
                f"slot ids {slot_ids.tolist()} out of range [0, {self.max_batch})"
            )

    # --- the first token, inside the insert program -----------------------
    # Both insert programs end the same way (_first_token): the head ran
    # over each row's last real position only, the rows' request keys
    # fold_in(rng, request_id) go into the session's slot_keys, and token
    # index 0 of each request's stream is sampled under fold_in(key, 0).
    # The scheduler's insert is then ONE program call and ONE fetch. What
    # the sampler needs rides the call as one dict of host arrays
    # (FirstToken), with a (rows, vocab) support mask only in a grammar lm.

    def _first_token(self, logits, slot_keys, slots, first, sampler):
        """Traced tail of an insert program: ``(first tokens (rows,) int32,
        slot_keys with the rows' request keys at slots)``, replicated. The
        derivation and the sampler are the eager ones the scheduler ran on
        the host before (so is every stream), under scope ``sampler``."""
        with jax.named_scope("sampler"):
            # both derivations at one shape: the second reuses the first's
            # trace and lowering (set-up time is Python time; an unrolled
            # threefry is ~250 lines of a TPU program)
            fold_in = jax.vmap(jax.random.fold_in)
            ids = first["request_ids"]
            keys = fold_in(jnp.broadcast_to(first["rng"], ids.shape), ids)
            sub = fold_in(keys, jnp.zeros_like(ids))
            slot_keys = slot_keys.at[slots].set(keys)
            # the keys are written BEFORE the sampler runs, and the barrier
            # keeps that order. With the key rows live beside the sampler's
            # (rows, vocab) temporaries the TPU compiler (libtpu 0.0.34)
            # dies in its memory-space assignment (a null dereference in the
            # best-fit repacker) on OLMoE-1B-7B's 8 x 128 insert: seen on the
            # chip, reproduced and cured by compiling every (rows, bucket)
            # insert of every cell for a described v5e. Values are untouched.
            slot_keys, logits, sub = jax.lax.optimization_barrier(
                (slot_keys, logits, sub))
            tokens = sampler(logits, sub, first["temperature"],
                             first["greedy"], allowed=first.get("allowed"))
            return self._replicate_out((tokens, slot_keys))

    def _first_lower(self, rows: int) -> tuple:
        """Lowering avals ``(slot_keys, first)`` of an insert program's
        sampling inputs, replicated under a mesh."""
        key = jax.eval_shape(jax.random.key, 0).dtype
        first = {"rng": jax.ShapeDtypeStruct((), key),
                 "request_ids": jax.ShapeDtypeStruct((rows,), jnp.uint32),
                 "temperature": jax.ShapeDtypeStruct((rows,), jnp.float32),
                 "greedy": jax.ShapeDtypeStruct((rows,), jnp.bool_)}
        if self.grammar:
            first["allowed"] = jax.ShapeDtypeStruct(
                (rows, self.config.vocab_size), jnp.bool_)
        return repl_avals(
            (jax.ShapeDtypeStruct((self.max_batch,), key), first))

    def _first_args(self, rows: int, first: Optional[FirstToken]) -> tuple:
        """``(sampler, first dict)`` for an insert program's call. Without
        sampling inputs (a caller that reads the logits): the default
        sampler, so the scheduler's programs are shared, greedy rows, zero
        request ids."""
        if first is None:
            if self._default_first_rng is None:
                self._default_first_rng = jax.random.key(0)
            first = FirstToken(
                self._default_first_rng, np.zeros((rows,), np.uint32),
                np.ones((rows,), np.float32), np.ones((rows,), bool))
        args = {"rng": first.rng,
                "request_ids": np.asarray(first.request_ids, np.uint32),
                "temperature": np.asarray(first.temperature, np.float32),
                "greedy": np.asarray(first.greedy, bool)}
        if self.grammar:
            args["allowed"] = (
                np.ones((rows, self.config.vocab_size), bool)
                if first.allowed is None else np.asarray(first.allowed, bool))
        return first.sampler, args

    @staticmethod
    def _insert_key(rows: int, bucket: int, sampler: Optional[SlotSampler]):
        """One insert program per (rows, bucket); an engine-wide top-k/top-p
        sampler (static in the program) has programs of its own."""
        if sampler is None or sampler == SlotSampler():
            return (rows, bucket)
        return (rows, bucket, sampler)

    def _insert_programs(self, rows: int, bucket: int,
                         sampler: Optional[SlotSampler] = None):
        """Lazily compile the RIGHT-SIZED slab insert for ``rows`` prompts:
        ONE donated program that prefills at batch width ``rows`` (prefill
        FLOPs scale with what was actually inserted, not ``max_batch``),
        scatters the fresh rows into the session cache per slot (O(rows) HBM
        traffic) and ends in :meth:`_first_token`. ``(params, cache,
        slot_keys, first, ids, slots, new_len[, *ad]) -> (first tokens,
        last-position logits (rows, vocab), cache, slot_keys)``."""
        key = self._insert_key(rows, bucket, sampler)
        if key in self._slab_insert:
            return self._slab_insert[key]
        sampler = sampler or SlotSampler()

        def insert_fn(params, cache, slot_keys, first, ids, slots, new_len,
                      *ad):
            logits, mut = self.model.apply(
                self._ad_vars(params, None, ad), ids,
                jnp.maximum(new_len - 1, 0), method="last_logits",
                mutable=["cache"])
            tokens, slot_keys = self._first_token(
                logits, slot_keys, slots, first, sampler)
            # pinned to the serving specs like every returned cache: the
            # AOT session programs reject any other layout; the constraint
            # reshards only the inserted rows
            return tokens, logits, self._shard_out(_scatter_cache_rows(
                cache, mut["cache"], slots, new_len, rows)), slot_keys

        self._slab_insert[key] = self._time_compile(
            f"insert_r{rows}_b{bucket}",
            lambda: self._compile(
                insert_fn, self._cache_avals(), *self._first_lower(rows),
                jnp.zeros((rows, bucket), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                *self._ad_lower(rows), donate_argnums=(1, 2)))
        return self._slab_insert[key]

    def _replicate_out(self, tree: PyTree) -> PyTree:
        """Inside-jit constraint forcing every leaf fully replicated when a
        device mesh is active (no-op otherwise) — kept for programs whose
        outputs must stay replicated regardless of the serving specs (and
        as the historical boundary the static rule also accepts)."""
        return replicate_out(tree)

    def _shard_out(self, tree: PyTree) -> PyTree:
        """Inside-jit constraint pinning every leaf of a returned serving
        collection to its derived TP spec (no-op off-mesh) — session-cache-
        producing programs must hand back exactly the layout the AOT
        session programs were lowered with (``_cache_avals`` /
        ``_adapter_avals`` / ``_gr_lower`` pin the inputs; this pins the
        outputs; inference/partition.py is the one spec source)."""
        return shard_out(tree)

    def _paged_insert_programs(self, rows: int, bucket: int,
                               sampler: Optional[SlotSampler] = None):
        """Lazily compile the paged insert for ``rows`` prompts at suffix
        width ``bucket``: ONE donated program that (a) prefills the suffix
        tokens at their own batch width, reading shared prefix pages through
        the rows' block tables (prefix-hit TTFT = suffix prefill only), (b)
        writes the fresh K/V straight into the session's page pool (no
        separate scatter pass — the pool is global, so the prefill IS the
        scatter; the donated pool is the layer scan's carry, so only the
        rows written move), (c) updates the session-width
        cache_index/block_table rows at ``slots``, and (d) ends in
        :meth:`_first_token`: the head over each row's last real position
        (``new_len - starts - 1``) only. ``(params, cache, slot_keys, first,
        ids, tables, slots, starts, new_len[, *ad]) -> (first tokens,
        last-position logits (rows, vocab), cache, slot_keys[, sums])``.

        A model with experts (``self.moe_stats``) is told which tokens are
        real (each row's ``new_len - starts`` suffix; the bucket's padding
        chooses no expert) and returns one more value, LAST: ``(5,) int32``,
        the sums of the fused session decode taken over the real tokens
        (expert slots touched, assignments, layers run), the grouped rows the
        experts were handed, real or not (layers x rows x bucket x top_k), and
        the rows the grouped kernel's dots ran over
        (``moe/expert_mlps.py::grouped_rows_multiplied``: the sub-tiles each
        layer's groups touch). Where a share of the experts is held it is
        ``(7,)``: the fourth routing sum, then what the layers' compact passes
        really handled (``moe/expert_mlps.py::share_call_sums``: passes x the
        row bound, the rows multiplied at the passes' tile, the passes).

        A model with per-slot state (``self.slot_rows``) is told the same
        (``self.wants_live``), runs over its rows' state gathered at ``slots``
        (zeros where ``starts`` is 0) and has it scattered back there, and
        returns after the above ``(2,) int32``: the real tokens its recurrence
        scanned and the positions it ran over (rows x the bucket, rounded up
        to its chunk)."""
        key = self._insert_key(rows, bucket, sampler)
        if key in self._paged_insert:
            return self._paged_insert[key]
        sampler = sampler or SlotSampler()
        ppseq = self.config.max_seq_len // self.config.page_size
        moe = self.moe_stats
        share = self.moe_share          # the expert layers hold a share of the routed
        state_leaves = self._slot_row_ends

        def insert_fn(params, cache, slot_keys, first, ids, tables, slots,
                      starts, new_len, *ad):
            def as_rows(path, leaf):
                p = jax.tree_util.keystr(path)
                if p.endswith("['cache_index']"):
                    return jnp.broadcast_to(
                        starts.astype(leaf.dtype), (leaf.shape[0], rows))
                if p.endswith("['block_table']"):
                    return jnp.broadcast_to(
                        tables[None], (leaf.shape[0], rows, ppseq))
                if state_leaves and p.endswith(state_leaves):
                    # the rows' state: a fresh request starts from zero
                    # whatever the slot's last tenant left, a chunked
                    # extend (starts > 0) continues
                    with jax.named_scope("state_rows"):
                        return _state_rows(leaf, slots, starts)
                return leaf  # the pool itself is batch-independent

            with jax.named_scope("cache_rows"):
                row_cache = jax.tree_util.tree_map_with_path(as_rows, cache)
            # a row's own suffix is real, the bucket's padding is not
            live = (jnp.arange(bucket)[None, :] < (new_len - starts)[:, None]
                    if self.wants_live else None)
            logits, mut = self.model.apply(
                self._ad_vars(params, row_cache, ad), ids,
                jnp.maximum(new_len - starts - 1, 0),
                **({"live": live} if self.wants_live else {}), method="last_logits",
                mutable=["cache", "moe_stats"] if moe else ["cache"])
            tokens, slot_keys = self._first_token(
                logits, slot_keys, slots, first, sampler)
            sums = ()
            if moe:
                with jax.named_scope("bookkeeping"):
                    top_k = min(self.config.top_k, self.config.num_experts)
                    chosen, routed, zero = _chosen(mut["moe_stats"], live)
                    if not share:          # every expert held: every pick is a row
                        sizes = jnp.sum(chosen, axis=1, dtype=jnp.int32)  # (layers, E)
                        grouped_rows = sizes.shape[0] * rows * bucket * top_k
                    parts = [_routing_sums(chosen, routed, zero, live)]
                    if share:              # the passes' rows, and the passes
                        parts.append(share_call_sums(chosen, top_k, self.moe_width))
                    else:
                        parts += [jnp.full((1,), grouped_rows, jnp.int32),
                                  grouped_rows_multiplied(sizes, rows * bucket, top_k
                                                          ).reshape(1)]
                    sums = self._replicate_out((jnp.concatenate(parts),))
            if self.scans:
                with jax.named_scope("bookkeeping"):
                    sums = (*sums, *self._replicate_out((jnp.stack([
                        jnp.sum(new_len - starts),
                        rows * self.config.scan_positions(bucket)
                    ]).astype(jnp.int32),)))

            def back(path, old, new):
                p = jax.tree_util.keystr(path)
                if p.endswith("['cache_index']"):
                    out = old
                    for i in range(rows):
                        v = jnp.broadcast_to(new_len[i].astype(old.dtype),
                                             (old.shape[0], 1))
                        out = jax.lax.dynamic_update_slice_in_dim(
                            out, v, slots[i], axis=1)
                    return out
                if p.endswith("['block_table']"):
                    out = old
                    for i in range(rows):
                        v = jnp.broadcast_to(
                            tables[i].astype(old.dtype)[None, None],
                            (old.shape[0], 1, ppseq))
                        out = jax.lax.dynamic_update_slice_in_dim(
                            out, v, slots[i], axis=1)
                    return out
                if state_leaves and p.endswith(state_leaves):
                    with jax.named_scope("state_rows"):
                        return old.at[:, slots].set(new)
                return new  # mutated pool leaves

            with jax.named_scope("table_write"):
                return (tokens, logits, self._shard_out(
                    jax.tree_util.tree_map_with_path(back, cache,
                                                     mut["cache"])),
                        slot_keys, *sums)

        self._paged_insert[key] = self._time_compile(
            f"paged_insert_r{rows}_b{bucket}",
            lambda: self._compile(
                insert_fn, self._cache_avals(), *self._first_lower(rows),
                jnp.zeros((rows, bucket), jnp.int32),
                jnp.zeros((rows, ppseq), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                *self._ad_lower(rows), donate_argnums=(1, 2)))
        return self._paged_insert[key]

    def _chunk_extend_programs(self, rows: int, bucket: int):
        """Lazily compile the CHUNKED-PREFILL extend for ``rows`` slots at
        chunk width ``bucket`` (contiguous-slab path): ONE donated program
        that (a) gathers the target slots' cache rows (O(rows) slices, not a
        whole-cache copy), pinning their ``cache_index`` to ``starts`` so
        the model writes the chunk at positions ``starts..starts+bucket``
        and attends it against everything already in the row, (b) runs the
        decode-mode forward at batch width ``rows``, and (c) scatters the
        mutated rows back with ``cache_index = new_len`` (the TRUE covered
        length — the pad tail's garbage writes land beyond it, behind the
        position mask, exactly like one-shot insert pads).

        Because per-position math is row- and width-local (dense cached
        attention reduces over the full ``max_seq_len`` key axis in both
        paths), a prompt prefilled through N chunk extends produces
        bit-identical KV and last-token logits to the one-shot insert of the
        whole prompt — the chunked-prefill exactness oracle
        (tests/test_chunked_prefill.py)."""
        key = (rows, bucket)
        if key in self._chunk_extend:
            return self._chunk_extend[key]

        def extend_fn(params, cache, ids, slots, starts, new_len, *ad):
            def gather(path, leaf):
                if jax.tree_util.keystr(path).endswith("['cache_index']"):
                    return jnp.broadcast_to(
                        starts.astype(leaf.dtype), (leaf.shape[0], rows))
                picked = [jax.lax.dynamic_slice_in_dim(leaf, slots[i], 1, axis=1)
                          for i in range(rows)]
                return jnp.concatenate(picked, axis=1)

            row_cache = jax.tree_util.tree_map_with_path(gather, cache)
            logits, mut = self.model.apply(
                self._ad_vars(params, row_cache, ad), ids,
                mutable=["cache"])

            def back(path, old, new):
                if jax.tree_util.keystr(path).endswith("['cache_index']"):
                    out = old
                    for i in range(rows):
                        v = jnp.broadcast_to(new_len[i].astype(old.dtype),
                                             (old.shape[0], 1))
                        out = jax.lax.dynamic_update_slice_in_dim(
                            out, v, slots[i], axis=1)
                    return out
                out = old
                for i in range(rows):
                    out = jax.lax.dynamic_update_slice_in_dim(
                        out, jax.lax.dynamic_slice_in_dim(new, i, 1, axis=1),
                        slots[i], axis=1)
                return out

            return logits, self._shard_out(
                jax.tree_util.tree_map_with_path(back, cache, mut["cache"]))

        self._chunk_extend[key] = self._time_compile(
            f"chunk_extend_r{rows}_b{bucket}",
            lambda: self._compile(
                extend_fn, self._cache_avals(),
                jnp.zeros((rows, bucket), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                jnp.zeros((rows,), jnp.int32),
                *self._ad_lower(rows), donate_argnums=(1,)))
        return self._chunk_extend[key]

    def extend(self, session: "DecodeSession", slot_ids: np.ndarray,
               chunk_ids: np.ndarray, lengths: np.ndarray,
               starts: np.ndarray, tables: Optional[np.ndarray] = None,
               adapter_slots: Optional[np.ndarray] = None,
               first: Optional[FirstToken] = None) -> jax.Array:
        """Chunked-prefill extension: write ``lengths[i]`` new prompt tokens
        per slot at positions ``starts[i]..starts[i]+lengths[i]`` (the
        tentpole primitive behind ``ServeEngine(prefill_chunk_tokens=...)``).
        Unlike :meth:`insert`, the slot's EXISTING KV is kept and extended —
        the chunk attends against it — and no first-token sample should be
        drawn until the final chunk. Returns the logits at each row's last
        real chunk token (meaningful only on a request's final chunk).

        Paged mode reuses the donated paged-insert program (it already
        prefills at arbitrary ``starts`` through caller-provided block
        tables — pass ``tables`` covering everything written through this
        chunk; the engine drives page allocation chunk-by-chunk via
        ``PagedKVCache.begin/extend/finish_chunked``), which also writes the
        rows' request keys into ``session.slot_keys`` (``first``, as
        :meth:`insert` takes it). Contiguous mode runs the
        gather/extend/scatter program of :meth:`_chunk_extend_programs`.
        """
        if self._decode is None:
            self.compile()
        slot_ids = np.asarray(slot_ids, np.int32)
        self._check_slots(slot_ids)
        rows, s = chunk_ids.shape
        if rows != len(slot_ids):
            raise ValueError(f"{rows} chunks for {len(slot_ids)} slots")
        lengths = np.asarray(lengths, np.int32)
        starts = np.asarray(starts, np.int32)
        if (lengths < 1).any():
            raise ValueError(f"empty chunk in {lengths.tolist()}")
        new_len = starts + lengths
        if not self.slot_rows_continue and (starts > 0).any():
            raise ValueError(
                f"{type(self.config).__name__} keeps {self.slot_rows} a slot, written "
                "by a whole prompt from position 0: a chunk that continues a row "
                f"(starts {starts.tolist()}) is not served")
        if int(new_len.max()) >= self.config.max_seq_len:
            raise ValueError(
                f"chunk end {int(new_len.max())} leaves no decode room in "
                f"max_seq_len {self.config.max_seq_len}")
        bucket = self._bucket_for(s)
        ids = np.zeros((rows, bucket), np.int32)
        ids[:, :s] = chunk_ids
        ad = self._ad_args(session.adapters,
                           adapter_slots if adapter_slots is not None
                           else np.zeros((rows,), np.int32))
        session.insert_fresh = not starts.any()
        if self.paged:
            if session.paged is None:
                raise ValueError("paged CausalLM needs a session from "
                                 "start_session() (no paged state attached)")
            if tables is None:
                raise ValueError("paged extend needs per-row block tables")
            sampler, first = self._first_args(rows, first)
            prog = self._paged_insert_programs(rows, bucket, sampler)
            (session.first_tokens, logits, session.cache, session.slot_keys,
             *sums) = prog(
                self.params, session.cache, session.slot_keys, first, ids,
                np.asarray(tables, np.int32), slot_ids, starts, new_len, *ad)
            self._keep_insert_sums(session, sums)
            session.insert_ran = (int(lengths.sum()), rows * bucket)
            session.lengths[slot_ids] = new_len
            return logits
        prog = self._chunk_extend_programs(rows, bucket)
        logits, session.cache = prog(
            self.params, session.cache, ids, slot_ids, starts, new_len, *ad)
        session.lengths[slot_ids] = new_len
        last = jnp.asarray(np.maximum(lengths - 1, 0))
        return logits[jnp.arange(rows), last]

    def _keep_insert_sums(self, session: "DecodeSession", sums) -> None:
        """What a paged insert returned after its four outputs, left on the
        device for the scheduler's one fetch: the routing sums of a model
        with experts, then the scan sums of a model with per-slot state."""
        sums = list(sums)
        session.insert_routing = sums.pop(0) if self.moe_stats else None
        session.insert_scanned = sums.pop(0) if self.scans else None

    def _insert_paged(self, session: "DecodeSession", slot_ids: np.ndarray,
                      prompt_ids: np.ndarray, lengths: np.ndarray,
                      reserve_tokens,
                      adapter_slots: Optional[np.ndarray] = None,
                      ns: Optional[Sequence[Optional[str]]] = None,
                      first: Optional[FirstToken] = None) -> jax.Array:
        """Paged admission: per-row prefix lookup + page allocation (host),
        then ONE suffix-width prefill-and-scatter program. ``reserve_tokens``
        (scalar or per-row) bounds the decode room reserved in pages —
        writes past it land in the slot's scratch page, never a neighbour.
        Raises :class:`PagePoolExhausted` BEFORE any device work when the
        pool (after LRU eviction of cache-only prefix pages) cannot cover
        the whole group — the scheduler defers and retries."""
        pkv = session.paged
        rows = len(slot_ids)
        if reserve_tokens is None:
            totals = np.full((rows,), self.config.max_seq_len, np.int64)
        else:
            totals = lengths.astype(np.int64) + np.broadcast_to(
                np.asarray(reserve_tokens, np.int64), (rows,))
        # per-row adapter namespace for the radix walk: prefix KV is
        # adapter-specific, so reuse is scoped to (tokens, adapter)
        nss = list(ns) if ns is not None else [None] * rows
        plans = []
        with pkv.span("cache_plan", rows=rows):
            try:
                for i in range(rows):
                    plans.append(pkv.plan(
                        prompt_ids[i, : lengths[i]].tolist(), int(totals[i]),
                        ns=nss[i]))
            except Exception:
                for p in plans:
                    pkv.rollback(p)
                raise
        starts = np.asarray([p.start for p in plans], np.int32)
        suffix = lengths - starts                      # >= 1 by plan()'s clamp
        bucket = self._bucket_for(int(suffix.max()))
        ids = np.zeros((rows, bucket), np.int32)
        for i in range(rows):
            ids[i, : suffix[i]] = prompt_ids[i, starts[i]: lengths[i]]
        tables = np.stack([pkv.table_for(int(slot_ids[i]), plans[i])
                           for i in range(rows)])
        try:
            sampler, first = self._first_args(rows, first)
            prog = self._paged_insert_programs(rows, bucket, sampler)
            (session.first_tokens, logits, session.cache, session.slot_keys,
             *sums) = prog(
                self.params, session.cache, session.slot_keys, first, ids,
                tables, slot_ids, starts, lengths,
                *self._ad_args(session.adapters,
                               adapter_slots if adapter_slots is not None
                               else np.zeros((rows,), np.int32)))
        except Exception:
            # the program (or its compile) failed AFTER planning took page
            # holds: release them or the pool leaks one admission's
            # footprint per failed dispatch — exactly the storm a chaos run
            # drives. The session cache may be unusable (donation), but the
            # host allocator must stay consistent for recovery.
            for p in plans:
                pkv.rollback(p)
            raise
        self._keep_insert_sums(session, sums)
        session.insert_ran = (int(suffix.sum()), rows * bucket)
        session.insert_fresh = not starts.any()
        with pkv.span("cache_commit", rows=rows):
            for i in range(rows):
                pkv.commit(int(slot_ids[i]), plans[i],
                           prompt_ids[i, : lengths[i]].tolist(), ns=nss[i])
        session.lengths[slot_ids] = lengths
        session.active[slot_ids] = True
        return logits

    def insert(self, session: "DecodeSession", slot_ids: np.ndarray,
               prompt_ids: np.ndarray, lengths: Optional[np.ndarray] = None,
               pad_token_id: int = 0,
               reserve_tokens: Optional[Any] = None,
               adapter_slots: Optional[np.ndarray] = None,
               ns: Optional[Sequence[Optional[str]]] = None,
               first: Optional[FirstToken] = None) -> jax.Array:
        """Prefill ``slot_ids`` with new prompts; every OTHER slot's cache
        rows and lengths are preserved (they may be mid-generation).

        Right-sized: only the inserted rows are prefilled — at their own
        batch width — and scattered into the session cache with per-slot
        ``dynamic_update_slice``, so both the prefill FLOPs and the cache
        HBM traffic scale with ``len(slot_ids)``, not ``max_batch`` (the
        reference prefills its full CTX batch per insert; the old path here
        did too, plus a whole-cache ``jnp.where`` copy).

        ONE program call, and nothing else on the device: the program runs
        the head over each row's last real position only, samples the rows'
        first tokens as ``first`` says (left on the device as
        ``session.first_tokens``) and writes their request keys into
        ``session.slot_keys``. Returns ``next_token_logits (len(slot_ids),
        vocab)``, on the device too."""
        if self._decode is None:
            self.compile()
        slot_ids = np.asarray(slot_ids, np.int32)
        self._check_slots(slot_ids)
        b, s = prompt_ids.shape
        if b != len(slot_ids):
            raise ValueError(f"{b} prompts for {len(slot_ids)} slots")
        if lengths is None:
            lengths = infer_prompt_lengths(prompt_ids, pad_token_id)
        lengths = np.maximum(np.asarray(lengths, np.int32), 1)
        if int(lengths.max()) >= self.config.max_seq_len:
            raise ValueError(
                f"prompt length {int(lengths.max())} leaves no decode room in "
                f"max_seq_len {self.config.max_seq_len}"
            )
        if self.paged:
            if session.paged is None:
                raise ValueError("paged CausalLM needs a session from "
                                 "start_session() (no paged state attached)")
            return self._insert_paged(session, slot_ids, prompt_ids, lengths,
                                      reserve_tokens,
                                      adapter_slots=adapter_slots, ns=ns,
                                      first=first)
        bucket = self._bucket_for(s)
        rows = len(slot_ids)
        sampler, first = self._first_args(rows, first)
        prog = self._insert_programs(rows, bucket, sampler)
        ids = np.zeros((rows, bucket), np.int32)
        ids[:, :s] = prompt_ids
        (session.first_tokens, logits, session.cache,
         session.slot_keys) = prog(
            self.params, session.cache, session.slot_keys, first, ids,
            slot_ids, lengths,
            *self._ad_args(session.adapters,
                           adapter_slots if adapter_slots is not None
                           else np.zeros((rows,), np.int32)))
        session.insert_ran = (int(lengths.sum()), rows * bucket)
        session.insert_fresh = True
        session.lengths[slot_ids] = lengths
        session.active[slot_ids] = True
        return logits

    def step(self, session: "DecodeSession", tokens: np.ndarray,
             adapter_slots: Optional[np.ndarray] = None) -> jax.Array:
        """One decode step for ALL slots (inactive slots advance harmlessly —
        mask their outputs caller-side). ``tokens``: (max_batch,). Raises
        — WITHOUT mutating any accounting — when an ACTIVE slot would write
        past ``max_seq_len`` (re-insert or retire it first; the scatter would
        otherwise drop silently)."""
        over = session.active & (session.lengths + 1 >= self.config.max_seq_len)
        if over.any():
            raise ValueError(
                f"slots {np.nonzero(over)[0].tolist()} exhausted max_seq_len "
                f"{self.config.max_seq_len}: re-insert or retire them"
            )
        logits, cache = self._decode(
            self.params, session.cache,
            jnp.asarray(tokens, jnp.int32).reshape(-1, 1),
            *self._live_args(session.active),
            *self._ad_args(session.adapters,
                           adapter_slots if adapter_slots is not None
                           else np.zeros((self.max_batch,), np.int32))
        )
        # account only after the decode actually executed
        session.cache = cache
        session.lengths += 1
        return logits[:, 0]

    def retire(self, session: "DecodeSession", slot_ids) -> None:
        """Mark slots idle (stops their overflow accounting; their cache rows
        are reused by the next insert). Idempotent and empty-safe — serving
        loops call this with 'whatever finished this iteration'."""
        slot_ids = np.asarray(slot_ids, np.int32).reshape(-1)
        if len(slot_ids) == 0:
            return
        if (slot_ids < 0).any() or (slot_ids >= self.max_batch).any():
            raise ValueError(
                f"slot ids {slot_ids.tolist()} out of range [0, {self.max_batch})"
            )
        session.active[slot_ids] = False
        if self.paged and session.paged is not None:
            # return pages to the free list (prefix-cached pages stay
            # resident for future hits) and point the retired slots' DEVICE
            # tables back at scratch, so a retired slot's residual decode
            # writes can never bleed into pages a later request reuses
            for slot in slot_ids:
                session.paged.release(int(slot))
            session.cache = _set_block_tables(session.cache,
                                              session.paged.tables)

    # --- generation ------------------------------------------------------

    def generate(
        self,
        prompt_ids: np.ndarray,
        max_new_tokens: int,
        sampler: Optional[Sampler] = None,
        eos_token_id: Optional[int] = None,
        rng: Optional[jax.Array] = None,
        lengths: Optional[np.ndarray] = None,
        pad_token_id: int = 0,
        fused_chunk: int = 0,
    ) -> GenerationResult:
        """Batched generate (reference runner.generate / benchmark path).
        ``prompt_ids``: (b, s) right-padded with ``pad_token_id``. Pass
        explicit per-prompt ``lengths`` when the pad id can legitimately
        appear inside a prompt — otherwise lengths are inferred from the
        rightmost non-pad position.

        ``fused_chunk > 1`` decodes in K-token fused device programs
        (``compile_decode_fused``): one dispatch + host read per K tokens
        instead of per token. Works with ANY sampler (the scan body carries
        the rng and splits per step in the stepwise order) and handles EOS
        per token inside the scan (post-EOS emissions frozen to
        ``pad_token_id``) — output is token-identical to the stepwise path;
        the device may still compute (never return) up to K-1 tokens past
        the point where every row finished."""
        if self.paged:
            raise ValueError(
                "generate() runs the contiguous-slot path; a paged CausalLM "
                "serves through sessions (insert/step) or ServeEngine")
        if self._decode is None:
            self.compile()
        sampler = sampler or Sampler(greedy=True)
        use_fused = fused_chunk and fused_chunk > 1
        rng = rng if rng is not None else jax.random.key(0)
        b, s = prompt_ids.shape
        if b > self.max_batch:
            raise ValueError(f"batch {b} exceeds max_batch {self.max_batch}")
        if lengths is None:
            lengths = infer_prompt_lengths(prompt_ids, pad_token_id)
        lengths = np.maximum(np.asarray(lengths, np.int32), 1)
        if lengths.shape != (b,):
            raise ValueError(f"lengths shape {lengths.shape} != ({b},)")
        if int(lengths.max()) + max_new_tokens > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({int(lengths.max())}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len {self.config.max_seq_len}: KV-cache writes "
                f"past the cache would be silently dropped"
            )
        bucket = self._bucket_for(s)
        ids = np.zeros((self.max_batch, bucket), np.int32)
        ids[:b, :s] = prompt_ids

        # adapter-enabled lms generate as the BASE model (identity slot 0 —
        # the correction is exactly zero); serving with real adapters goes
        # through sessions / ServeEngine.submit(adapter=)
        ad = self._ad_args(None, np.zeros((self.max_batch,), np.int32))
        logits, cache = self._prefill[bucket](self.params, jnp.asarray(ids),
                                              *ad)
        full_lengths = np.zeros((self.max_batch,), np.int32)
        full_lengths[:b] = lengths
        cache = _set_cache_index(cache, jnp.asarray(full_lengths))
        # next-token logits at each slot's last REAL token
        last = jnp.asarray(np.maximum(full_lengths - 1, 0))
        step_logits = logits[jnp.arange(self.max_batch), last]

        out = np.zeros((self.max_batch, max_new_tokens), np.int64)
        done = np.zeros((self.max_batch,), bool)
        done[b:] = True
        gen_len = np.zeros((self.max_batch,), np.int32)
        if max_new_tokens == 0:
            return GenerationResult(tokens=out[:b], lengths=gen_len[:b])

        def record(tok_np: np.ndarray, t: int) -> bool:
            nonlocal done, gen_len
            out[:, t] = np.where(done, pad_token_id, tok_np)
            gen_len = np.where(done, gen_len, gen_len + 1)
            if eos_token_id is not None:
                done = done | (tok_np == eos_token_id)
            return bool(done.all())

        rng, sub = jax.random.split(rng)
        tok_np = np.asarray(sampler(step_logits, sub))            # (max_batch,)
        finished = record(tok_np, 0)
        t = 1
        while t < max_new_tokens and not finished:
            # full chunks, then ONE tail-sized fused program for the
            # remainder (cached per size): short tails keep the dispatch
            # amortization instead of silently falling back to per-token
            # step decode; only a 1-token tail uses the step program
            k = min(fused_chunk, max_new_tokens - t) if use_fused else 1
            if k > 1:
                fused = self.compile_decode_fused(
                    k, sampler, eos_token_id, pad_token_id)
                toks, cache, next_tok, rng, _ = fused(
                    self.params, cache, jnp.asarray(tok_np[:, None], jnp.int32),
                    rng, jnp.asarray(done), *ad)
                for row in np.asarray(toks):                      # (K, max_batch)
                    finished = record(row, t)
                    t += 1
                    if finished:
                        break
                # raw last sampled token feeds the next program, matching
                # the stepwise feed discipline (rows already emitted masked)
                tok_np = np.asarray(next_tok)[:, 0]
                continue
            rng, sub = jax.random.split(rng)
            step_logits, cache = self._decode(
                self.params, cache, jnp.asarray(tok_np[:, None], jnp.int32),
                *ad
            )
            tok_np = np.asarray(sampler(step_logits[:, 0], sub))
            finished = record(tok_np, t)
            t += 1
        return GenerationResult(tokens=out[:b], lengths=gen_len[:b])
