"""Sharding-annotation helpers: the GSPMD face of tensor/sequence parallelism.

The reference expresses TP by hand-slicing weights per rank and issuing
collectives (``parallel_layers/layers.py``, ``utils.py:48`` TP attribute
tagging). On TPU the idiomatic mechanism is GSPMD: parameters carry a
``PartitionSpec`` (via ``flax.linen.with_partitioning`` metadata), activations
get ``with_sharding_constraint`` hints, and XLA's SPMD partitioner inserts and
overlaps the all-gather/reduce-scatter/all-reduce — including the async
grad-all-reduce trick the reference implements manually in
``LinearWithAsyncCommunication`` (layers.py:288-417), which XLA's
latency-hiding scheduler performs automatically.

This module centralizes the canonical activation specs and the helpers layers
use to apply them.
"""

from __future__ import annotations


import jax
from flax import linen as nn
from flax.core import meta
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.parallel.mesh import DP_AXES, TP_AXIS

# Canonical activation specs, (batch, seq, hidden) convention.
ACT_FULL = P(DP_AXES, None, None)      # batch over DP, rest replicated
ACT_TP = P(DP_AXES, None, TP_AXIS)     # hidden sharded over TP (between column/row linear)
ACT_SP = P(DP_AXES, TP_AXIS, None)     # sequence sharded over TP (Megatron SP regions)
ACT_CP = P(DP_AXES, "cp", None)        # sequence sharded over CP (ring attention)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """``with_sharding_constraint`` against the global mesh; no-op when
    parallel state is uninitialized (single-device unit tests).

    Inside a ``shard_map`` region the constraint is a GSPMD hint over the
    mesh axes the region left automatic: the pipeline engines are manual
    over ``pp`` only, so the TP/SP/DP specs of the layers they run still
    apply there. A spec that names a manual axis has nothing to say —
    the region already holds that axis's local shard (ring attention and
    the flash kernel's region are manual over every axis) — and is
    dropped."""
    if not ps.model_parallel_is_initialized():
        return x
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if manual:
        named = {a for entry in spec if entry is not None
                 for a in ((entry,) if isinstance(entry, str) else entry)}
        if named & manual or manual >= set(ps.get_mesh().axis_names):
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(ps.get_mesh(), spec))


def param_partition_specs(variables):
    """PartitionSpec pytree for a flax variable dict whose params were created
    with ``nn.with_partitioning`` (the TPU analogue of the reference's
    ``set_tensor_model_parallel_attributes``, parallel_layers/utils.py:48)."""
    return nn.get_partition_spec(variables)


def shard_variables(variables, mesh=None):
    """Device-put a boxed variable tree onto the mesh per its partition specs,
    returning an *unboxed* tree of global ``jax.Array``s."""
    mesh = mesh or ps.get_mesh()
    specs = nn.get_partition_spec(variables)
    unboxed = meta.unbox(variables)

    def _put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(_put, unboxed, specs)


def named_sharding_tree(variables, mesh=None):
    """NamedSharding pytree (for jit in_shardings/out_shardings) from a boxed
    variable tree."""
    mesh = mesh or ps.get_mesh()
    specs = nn.get_partition_spec(variables)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def specs_to_shardings(specs, mesh=None):
    """PartitionSpec tree -> NamedSharding tree; non-spec leaves (plain params
    without partitioning metadata) map to replicated. The single source of
    truth for this conversion — used by sharded init, the train step, and the
    pipeline model alike."""
    mesh = mesh or ps.get_mesh()
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if isinstance(s, P) else P()),
        specs,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )
