"""Parallel model wrapper (reference ``trainer/model.py`` ``NxDModel``:8 and
``trainer/trainer.py`` ``initialize_parallel_model``:141).

The reference's 6-phase init (meta-init → PP wrap → staggered materialize →
LoRA → pad → activation-ckpt wrap) collapses on TPU: jitting ``module.init``
with sharded ``out_shardings`` materializes every param directly as a global
sharded array on the mesh — no meta device, no sequential host→device moves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax.core import meta

from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.utils.compile_cache import compile_log

PyTree = Any

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def resolve_dtype(name) -> Any:
    return _DTYPES[name] if isinstance(name, str) else name


@dataclasses.dataclass
class ParallelModel:
    """Module + sharded params + their partition specs.

    ``apply`` mirrors the reference ``NxDModel``'s uniform call surface
    (trainer/model.py:34-39); params are global ``jax.Array``s laid out on
    the mesh per the specs the layers declared via ``nn.with_partitioning``.

    When the config carried a ``lora_config`` (reference trainer.py phase 4,
    LoraModel wrap), ``lora_params`` holds the adapter tree and the train
    step differentiates ONLY it — the base stays frozen by construction.
    """

    module: nn.Module
    params: PyTree
    param_specs: PyTree
    mesh: jax.sharding.Mesh
    lora_config: Optional[Any] = None
    lora_params: Optional[PyTree] = None
    lora_specs: Optional[PyTree] = None

    def apply(self, params: PyTree, *args, **kwargs):
        return self.module.apply({"params": params}, *args, **kwargs)

    def param_shardings(self) -> PyTree:
        from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

        return specs_to_shardings(self.param_specs, self.mesh)

    @property
    def trainable_params(self) -> PyTree:
        return self.lora_params if self.lora_config is not None else self.params

    @property
    def trainable_specs(self) -> PyTree:
        return self.lora_specs if self.lora_config is not None else self.param_specs

    def trainable_shardings(self) -> PyTree:
        from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

        return specs_to_shardings(self.trainable_specs, self.mesh)

    def merged_params(self, lora_params: Optional[PyTree] = None) -> PyTree:
        """Full params with the adapter delta folded in (reference
        merge_lora:357); identity when LoRA is off."""
        if self.lora_config is None:
            return self.params
        from neuronx_distributed_tpu.lora.core import merge_lora

        return merge_lora(
            self.params,
            self.lora_params if lora_params is None else lora_params,
            self.lora_config,
        )

    def num_params(self) -> int:
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.params))


def _apply_config_overrides(module: nn.Module, nxd_config: Dict[str, Any]) -> nn.Module:
    """Make the trainer config REAL on the model (reference trainer.py phases
    4-6 wire lora/pad/activation-ckpt; here dtype + remat + SP ride on the
    model's own dataclass config). Only keys the user explicitly set are
    applied, so model-level choices are never silently clobbered by defaults.
    Requires the module to expose a dataclass ``config`` and be rebuildable
    as ``type(module)(new_config)`` (all in-repo model families are)."""
    cfg = getattr(module, "config", None)
    if cfg is None or not dataclasses.is_dataclass(cfg):
        return module
    over: Dict[str, Any] = {}
    mp = nxd_config.get("mixed_precision_config", {})
    explicit = nxd_config.get("_explicit_keys", {})
    for mp_key, field in (("compute_dtype", "dtype"), ("param_dtype", "param_dtype")):
        if mp_key in explicit.get("mixed_precision_config", ()) and hasattr(cfg, field):
            over[field] = resolve_dtype(mp[mp_key])
    ac = nxd_config.get("activation_checkpoint_config")
    if ac is not None and hasattr(cfg, "remat_policy"):
        over["remat_policy"] = ac
    if explicit.get("sequence_parallel") and hasattr(cfg, "sequence_parallel"):
        over["sequence_parallel"] = bool(nxd_config.get("sequence_parallel"))
    # key on the MESH's cp size, not the config's: a user who initialized the
    # mesh directly (cp>1) with a default config must still get the CP path —
    # a cp axis without ring attention silently replicates the whole forward
    cp = ps.get_context_parallel_size() if ps.model_parallel_is_initialized() else (
        nxd_config.get("context_parallel_size", 1))
    if cp > 1 and hasattr(cfg, "context_parallel"):
        over["context_parallel"] = True
    if not over:
        return module
    return type(module)(dataclasses.replace(cfg, **over))


def initialize_parallel_model(
    nxd_config: Dict[str, Any],
    module_fn: Callable[[], nn.Module],
    *example_args,
    rngs: Optional[Dict[str, jax.Array]] = None,
    **example_kwargs,
) -> ParallelModel:
    """Build + shard-initialize a model (reference trainer/trainer.py:141).

    Initializes parallel state from the config if needed, then jits
    ``module.init`` with sharded out_shardings so each param is *born* on its
    mesh shard (replacing reference phases 1+3: meta init + staggered move,
    trainer.py:151-176, utils/model_utils.py:245,320). Applies
    mixed-precision / activation-checkpoint config overrides to the model
    config and injects LoRA adapters when ``lora_config`` is set (reference
    phases 4+6).

    What varies from run to run reaches the weights' program as ARGUMENTS:
    the keys of ``rngs`` and every array leaf (``jax.Array``,
    ``numpy.ndarray``) of the example arguments. A key closed over is a
    literal of the lowered program, so every seed was a program the
    persistent compile cache had never seen; as an argument, one cache entry
    serves every seed (the key's kind is part of its type: two kinds are two
    programs; an example the initialisers only take shapes from is pruned by
    ``jit``, so its shape is in the program only where the weights' shapes
    follow it). Anything else among the example arguments (a flag, ``None``,
    a Python number) decides the trace and stays in the closure. The same
    seed draws the same weights, bit for bit, as the closed-over form did.
    """
    if not ps.model_parallel_is_initialized():
        ps.initialize_model_parallel(
            tensor_model_parallel_size=nxd_config["tensor_parallel_size"],
            pipeline_model_parallel_size=nxd_config["pipeline_parallel_size"],
            expert_model_parallel_size=nxd_config["expert_parallel_size"],
            context_parallel_size=nxd_config.get("context_parallel_size", 1),
        )
    mesh = ps.get_mesh()
    module = _apply_config_overrides(module_fn(), nxd_config)
    seed = nxd_config.get("model_init_config", {}).get("seed", 0)
    rngs = rngs or {"params": jax.random.key(seed)}

    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    leaves, treedef = jax.tree_util.tree_flatten((example_args, example_kwargs))
    places = [i for i, leaf in enumerate(leaves) if isinstance(leaf, (jax.Array, np.ndarray))]
    arrays = [leaves[i] for i in places]

    def boxed_init(rngs, arrays):
        given = list(leaves)
        for i, array in zip(places, arrays):
            given[i] = array
        args, kwargs = treedef.unflatten(given)
        return module.init(rngs, *args, **kwargs)

    def init_fn(rngs, arrays):
        return meta.unbox(boxed_init(rngs, arrays))["params"]

    # the compile log's row of the weights' program: the abstract pass, the
    # program's stages, and (the rest of its wall) the call's dispatch
    with compile_log.program("init_params", group="weights"):
        # Abstract-eval once to learn shapes + partition metadata without FLOPs.
        with compile_log.stretch("abstract_ms"):
            abstract = jax.eval_shape(boxed_init, rngs, arrays)
        specs = nn.get_partition_spec(abstract)["params"]
        shardings = specs_to_shardings(specs, mesh)
        params = compile_log.staged(
            jax.jit(init_fn, out_shardings=shardings), rngs, arrays)(rngs, arrays)

    lora_cfg = nxd_config.get("lora_config")
    lora_params = lora_specs = None
    if lora_cfg is not None:
        from neuronx_distributed_tpu.lora.core import (
            LoraConfig,
            init_lora,
            lora_param_specs,
        )

        if isinstance(lora_cfg, dict):
            lora_cfg = LoraConfig(**lora_cfg)
        lora_params = init_lora(params, lora_cfg, jax.random.key(seed + 1))
        lora_specs = lora_param_specs(lora_params, params, specs)
        lora_params = jax.device_put(
            lora_params, specs_to_shardings(lora_specs, mesh)
        )
    return ParallelModel(
        module=module, params=params, param_specs=specs, mesh=mesh,
        lora_config=lora_cfg, lora_params=lora_params, lora_specs=lora_specs,
    )
