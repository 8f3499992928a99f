"""TP-sharded serving partition specs (the serving-side spec layer).

PR 3 pinned every serving collection — KV caches, adapter stacks,
grammar tables — fully REPLICATED at program boundaries. Correct, but it
caps the engine at models whose full KV fits one chip and leaves the
``tp`` axis idle at serve time. This module is the sharded replacement:
it derives a :class:`~jax.sharding.PartitionSpec` for every serving leaf
BY NAME, the way ``lora_param_specs`` (lora/core.py) derives adapter
specs from the base kernels — and the way the name-keyed ``SpecLayout``
matchers of serving systems do (cf. Pope et al., *Efficiently Scaling
Transformer Inference*; Shoeybi et al., *Megatron-LM* for the
column/row-parallel layer map the specs mirror).

The sharding story, per collection:

* **KV pools/slabs** (``cached_key``/``cached_value``): the KV-head axis
  (``-2`` in every layout — paged ``(L, npages, ps, n_kv, hd)``, slab
  ``(L, b, S, n_kv, hd)``, and any reshape that keeps the last two) shards
  over ``tp``, matching the GQA QKV projection's head split. Attention
  gathers index the PAGE axis, so every gather stays local per shard;
  one logical page id maps to one slice per shard and the host-side
  ``PageAllocator``/``RadixPrefixIndex`` stay shard-agnostic. int8
  pools' per-(page, kv-head) fp32 scale leaves
  (``cached_key_scale``/``cached_value_scale``) follow the same -2-axis
  rule, so a page's bytes and its scales never cross a chip boundary.
* **Adapter stacks** (``lora_<target>_{a,b}``): column-parallel targets
  (q/k/v/gate/up) shard the B fan-out (the base kernel's output split;
  A replicated); row-parallel targets (o_proj/down_proj) shard the A
  fan-in (the base kernel's input split; B replicated) — exactly the
  ``lora_param_specs`` training-side derivation, applied to the
  slot-stacked serving pools.
* **Grammar tables** (``need``/``next``): the vocab axis shards over
  ``tp`` so the budget-aware mask is computed pre-gather per shard,
  aligned with the vocab-sharded lm_head logits
  (``ColumnParallelLinear(gather_output=False)``).
* **Per-slot state** (a configuration's ``slot_row_leaves``, one row a slot
  of a recurrent layer): no rule here, so replicated; ``CausalLM`` refuses
  such a model at ``tp > 1`` rather than guess a spec.
* **Control leaves** (``block_table``/``cache_index``/``adapter_idx``/
  scales/``terminal``/budgets): tiny, host-written between blocks —
  replicated.

Divisibility is checked per leaf: a dim that does not divide the TP
degree falls back to replicated for that leaf — degraded capacity,
never a wrong answer (and ``tp == 1`` or no mesh degrades everything to
the PR 3 replicated layout, so off-mesh callers are byte-identical).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

PyTree = Any

# Projection targets whose BASE kernel is row-parallel (input-sharded):
# their LoRA A stack shards fan-in; every other target is column-parallel
# (output-sharded): its LoRA B stack shards fan-out. Mirrors
# lora_param_specs' kernel-spec derivation (lora/core.py).
ROW_PARALLEL_TARGETS = ("o_proj", "down_proj")

_LORA_LEAF = re.compile(r"\['lora_(\w+?)_(a|b|scale)'\]$")


def tp_degree() -> int:
    """Current tensor-parallel degree (1 off-mesh) — the one answer to
    "how many shards does a serving leaf split into right now", shared by
    spec derivation, per-shard sizing, and the disagg handoff framing."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return 1
    return ps.get_tensor_model_parallel_size()


_tp_degree = tp_degree


def _shardable(dim: int, tp: int) -> bool:
    return tp > 1 and dim % tp == 0


def leaf_partition_spec(path: str, shape, tp: int) -> PartitionSpec:
    """The serving spec for ONE leaf, keyed by its tree-path name (a
    ``jax.tree_util.keystr`` suffix or a bare ``['name']``). Replicated
    whenever the would-be sharded dim does not divide ``tp``."""
    from neuronx_distributed_tpu.models.llama import (
        KV_PAGE_LEAVES,
        KV_SCALE_LEAVES,
        leaf_paths,
    )

    nd = len(shape)
    if path.endswith(leaf_paths(KV_PAGE_LEAVES + KV_SCALE_LEAVES)):
        # int8 pools carry per-(page, kv-head) fp32 scale leaves shaped
        # (.., npages, 1, n_kv, 1): the n_kv axis sits at -2 exactly like
        # the pools, so one rule shards pool and scales congruently — a
        # shard's pages and their scales always live on the same chip.
        if nd >= 2 and _shardable(shape[-2], tp):
            return PartitionSpec(*([None] * (nd - 2)), "tp", None)
        return PartitionSpec()
    m = _LORA_LEAF.search(path)
    if m is not None:
        target, kind = m.group(1), m.group(2)
        if (kind == "a" and target in ROW_PARALLEL_TARGETS and nd == 4
                and _shardable(shape[2], tp)):
            # (L, slots, fan_in, r_max): fan-in split, like the base kernel
            return PartitionSpec(None, None, "tp", None)
        if (kind == "b" and target not in ROW_PARALLEL_TARGETS and nd == 4
                and _shardable(shape[3], tp)):
            # (L, slots, r_max, fan_out): fan-out split, like the base kernel
            return PartitionSpec(None, None, None, "tp")
        return PartitionSpec()
    if path.endswith("['need']") or path.endswith("['next']"):
        if nd >= 1 and _shardable(shape[-1], tp):
            return PartitionSpec(*([None] * (nd - 1)), "tp")
        return PartitionSpec()
    return PartitionSpec()


def serving_partition_specs(tree: PyTree) -> PyTree:
    """PartitionSpec per leaf of a serving collection (cache / adapter /
    grammar tree or any mix), derived by leaf name under the CURRENT
    parallel state (all-replicated off-mesh or at ``tp == 1``)."""
    tp = _tp_degree()

    def spec(path, leaf):
        return leaf_partition_spec(jax.tree_util.keystr(path), leaf.shape, tp)

    return jax.tree_util.tree_map_with_path(spec, tree)


def shard_out(tree: PyTree) -> PyTree:
    """Program-boundary sharding pin — the TP-sharded counterpart of
    ``causal_lm.replicate_out``: constrain every leaf of a returned
    serving collection to its derived spec (no-op off-mesh). Every
    compiled program that RETURNS a session cache / adapter / grammar
    collection routes it through this (or ``_replicate_out``) so GSPMD
    hands back exactly the layout the AOT session programs were lowered
    with (statically enforced by nxdcheck's cache-replication rule).
    Works inside jit (a layout constraint) and eagerly (acts like
    ``device_put``), so host-side re-pins share the one spec source."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return tree
    mesh = ps.get_mesh()
    tp = ps.get_tensor_model_parallel_size()

    def pin(path, leaf):
        spec = leaf_partition_spec(
            jax.tree_util.keystr(path), leaf.shape, tp)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(pin, tree)


def constrain_named(name: str, x: jax.Array) -> jax.Array:
    """In-graph pin for ONE named leaf — what the model's attention cache
    writes use on the ``cached_key``/``cached_value`` stack the layer scan
    carries (the axis-from-the-right spec rule makes the derivation the
    same whatever leads the KV-head axis). No-op off-mesh."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return x
    spec = leaf_partition_spec(
        f"['{name}']", x.shape, ps.get_tensor_model_parallel_size())
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ps.get_mesh(), spec))


def shard_avals(avals: PyTree) -> PyTree:
    """Attach the serving NamedShardings to a ``ShapeDtypeStruct`` tree —
    the lowering-time counterpart of :func:`shard_out`. AOT programs
    lowered on these avals then REQUIRE the sharded layout at call time
    (the PR 3 protection, with the sharded layout instead of forced
    replication). Identity off-mesh."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return avals
    mesh = ps.get_mesh()
    tp = ps.get_tensor_model_parallel_size()

    def pin(path, s):
        spec = leaf_partition_spec(jax.tree_util.keystr(path), s.shape, tp)
        return jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(pin, avals)


def repl_args(*args: Any) -> tuple:
    """Commit each (concrete) lowering example array fully REPLICATED —
    identity off-mesh. The row-state inputs of the fused session decode
    ((b,) control vectors, (b,1) tok, (b,) key rows) must not be left
    unannotated at ``lower`` time: GSPMD otherwise assigns them its own
    layout (observed: batch over 'edp' whenever max_batch divides it),
    which the ASYNC block loop — the one caller that feeds these slots
    COMMITTED arrays (block t's outputs, staged-override edits) — then
    trips at call time. Replicated in + replicated out (``replicate_out``
    on the row outputs) keeps the t→t+1 feedback loop sharding-stable."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return args
    repl = NamedSharding(ps.get_mesh(), PartitionSpec())
    return tuple(jax.device_put(a, repl) for a in args)


def repl_avals(avals: PyTree) -> PyTree:
    """``shard_avals``'s replicated counterpart for row-state
    ``ShapeDtypeStruct`` trees (the (rows,) adapter/grammar index vectors
    riding the session programs) — identity off-mesh."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    if not ps.model_parallel_is_initialized():
        return avals
    repl = NamedSharding(ps.get_mesh(), PartitionSpec())
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        avals)


def zeros_like_avals(avals: PyTree) -> PyTree:
    """All-zeros tree materialized WITH each aval's sharding (fresh
    session caches / identity pools must be born in the layout the AOT
    programs expect, not resharded on first call)."""

    def z(s):
        x = jnp.zeros(s.shape, s.dtype)
        sh = getattr(s, "sharding", None)
        return jax.device_put(x, sh) if sh is not None else x

    return jax.tree.map(z, avals)


def repin(tree: PyTree, like: PyTree) -> PyTree:
    """Restore each leaf's committed sharding after a host-side eager
    mutation (``.at[...].set`` on a sharded leaf may hand back a layout
    the AOT programs reject; ``device_put`` to the ORIGINAL leaf's
    sharding is the invariant-preserving fix). ``like`` is the
    pre-mutation tree; leaves whose sharding already matches pass
    through untouched."""

    def fix(new, old):
        sh = getattr(old, "sharding", None)
        if sh is None or getattr(new, "sharding", None) == sh:
            return new
        return jax.device_put(new, sh)

    return jax.tree.map(fix, tree, like)


def sharded_fraction(tree: PyTree) -> float:
    """Fraction of the tree's BYTES whose leaves carry a tp-sharded spec
    under the current state — the capacity-multiplication observability
    hook (per-shard bytes = global * (1 - f + f / tp))."""
    tp = _tp_degree()
    total = sharded = 0

    def visit(path, leaf):
        nonlocal total, sharded
        import numpy as np

        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        total += nbytes
        spec = leaf_partition_spec(jax.tree_util.keystr(path), leaf.shape, tp)
        if any(ax is not None for ax in spec):
            sharded += nbytes

    jax.tree_util.tree_map_with_path(visit, tree)
    return (sharded / total) if total else 0.0
