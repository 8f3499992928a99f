"""Multi-host bootstrap + per-host data feeding.

TPU-native counterpart of the reference's multi-node runtime surface: the
``torchrun``-launched process group init (reference
``src/neuronx_distributed/parallel_layers/parallel_state.py:60`` expects
``torch.distributed.init_process_group`` done by the launcher, e.g.
``examples/training/llama/tp_pp_llama_hf_pretrain/run_llama2_70B_tp_pp.sh``)
and the per-rank ``DistributedSampler`` data feeding of its examples.

On TPU pods the shape is different and simpler:

* every host runs the SAME single-controller program;
* :func:`initialize_distributed` wires the hosts into one JAX runtime
  (``jax.distributed.initialize``) so ``jax.devices()`` becomes the GLOBAL
  device list and one ``Mesh`` spans the pod;
* each host feeds only its local slice of the global batch;
  :func:`shard_host_batch` assembles the global ``jax.Array`` from the
  process-local rows (``jax.make_array_from_process_local_data``) — the
  multi-controller equivalent of the reference's DistributedSampler + DDP
  input scatter;
* collectives need no backend selection: XLA lowers them onto ICI within a
  slice and DCN across slices from the mesh itself (SURVEY §5.8).

Launch contract (mirrors the reference's ``torchrun --nnodes … --node_rank …
--master_addr …``): every host runs the same script with

    NXD_COORDINATOR_ADDRESS=<host0>:<port>
    NXD_NUM_PROCESSES=<num_hosts>
    NXD_PROCESS_ID=<this host's index>

or passes the equivalent keyword arguments. On Cloud TPU pods, where the
runtime can discover all three, ``initialize_distributed()`` with no
arguments and no env vars asks JAX to auto-detect (TPU backend only).
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger("nxd.distributed")

_INITIALIZED = False

# env names follow the reference's MASTER_ADDR/RANK/WORLD_SIZE trio
_ENV_COORD = "NXD_COORDINATOR_ADDRESS"
_ENV_NPROC = "NXD_NUM_PROCESSES"
_ENV_PID = "NXD_PROCESS_ID"


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> bool:
    """Join this process into the pod-wide JAX runtime.

    Resolution order per field: explicit argument → ``NXD_*`` env var →
    (TPU only) JAX auto-detection. Returns True when a multi-process runtime
    was (or already had been) initialized, False when running single-process
    (no coordinator configured anywhere) — so scripts can call this
    unconditionally, exactly like the reference examples always call
    ``init_process_group`` and torchrun decides the world size.
    """
    # NOTE: nothing in this function may touch the XLA backend (jax.devices,
    # jax.process_count, jax.default_backend, ...) before
    # jax.distributed.initialize — backend init must happen AFTER joining.
    global _INITIALIZED
    if _INITIALIZED or _runtime_already_joined():
        _INITIALIZED = True
        return jax.process_count() > 1

    coord = coordinator_address or os.environ.get(_ENV_COORD)
    nproc = num_processes if num_processes is not None else _env_int(_ENV_NPROC)
    pid = process_id if process_id is not None else _env_int(_ENV_PID)

    if coord is None and nproc is None and pid is None:
        # No explicit wiring. On a Cloud TPU pod the runtime can discover the
        # topology itself; anywhere else, stay single-process. Never
        # auto-join when the platform is pinned off-TPU (e.g. a --tiny CPU
        # smoke executed ON a pod worker): jax.distributed.initialize would
        # block at the coordinator barrier for peers that never start.
        if _platform_pinned_off_tpu():
            return False
        if _looks_like_tpu_pod():
            logger.info("distributed: pod topology detected, joining "
                        "(blocks until all workers start)")
            jax.distributed.initialize()
            _INITIALIZED = True
            logger.info(
                "distributed: auto-detected pod, process %d/%d",
                jax.process_index(), jax.process_count())
            return True
        return False
    if coord is None or nproc is None or pid is None:
        raise ValueError(
            "partial distributed config: need all of coordinator_address "
            f"({coord!r}), num_processes ({nproc!r}), process_id ({pid!r}) — "
            f"set {_ENV_COORD}/{_ENV_NPROC}/{_ENV_PID} or pass them explicitly")
    if int(nproc) == 1:
        return False  # single host launched through the pod contract

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(nproc),
        process_id=int(pid),
        local_device_ids=local_device_ids,
    )
    _INITIALIZED = True
    logger.info(
        "distributed: joined %s as process %d/%d (%d local / %d global devices)",
        coord, jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count())
    return True


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else None


def _runtime_already_joined() -> bool:
    """Whether jax.distributed.initialize already ran (e.g. by the launcher),
    WITHOUT initializing the XLA backend as jax.process_count() would."""
    try:
        from jax._src import distributed as _jd

        return _jd.global_state.client is not None
    except Exception:
        return False


def _platform_pinned_off_tpu() -> bool:
    """True when the user explicitly selected a non-TPU platform (config or
    env), read WITHOUT initializing the backend."""
    try:
        plats = jax.config.jax_platforms  # set by jax.config.update / env
    except AttributeError:
        plats = None
    plats = plats or os.environ.get("JAX_PLATFORMS") or ""
    return bool(plats) and "tpu" not in plats


def _looks_like_tpu_pod() -> bool:
    """Cloud TPU pod VMs list >1 worker in TPU_WORKER_HOSTNAMES (or set the
    megascale coordinator); a one-host machine lists only itself."""
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


# --- per-host batch feeding -------------------------------------------------

def shard_host_batch(batch: Any, mesh: Optional[Mesh] = None,
                     pspec: Optional[PartitionSpec] = None) -> Any:
    """Assemble global on-device batch arrays from this host's local rows.

    ``batch`` is a pytree of host-local numpy arrays whose leading dimension
    is this process's share of the global batch (global_batch = local_batch ×
    process_count, concatenated in process order). Leaves come back as global
    ``jax.Array``s sharded over the combined DP axes — the layout
    ``make_train_step`` expects — via
    ``jax.make_array_from_process_local_data``. Single-process this is a
    plain sharded ``device_put``, so callers use one code path everywhere.
    """
    mesh = mesh if mesh is not None else ps.get_mesh()

    def to_global(x):
        x = np.asarray(x)
        spec = pspec if pspec is not None else ps.data_pspec(*([None] * (x.ndim - 1)))
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree.map(to_global, batch)


def host_batch_slice(global_batch_size: int) -> slice:
    """Row slice of the global batch this process should feed (process-order
    concatenation contract of :func:`shard_host_batch`)."""
    n = jax.process_count()
    if global_batch_size % n != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by process count {n}")
    per = global_batch_size // n
    i = jax.process_index()
    return slice(i * per, (i + 1) * per)
