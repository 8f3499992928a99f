"""neuronx_distributed_tpu — a TPU-native distributed training & inference framework.

Capability surface mirrors AWS NeuronxDistributed (see SURVEY.md); the
implementation is idiomatic JAX/XLA: a ``jax.sharding.Mesh`` instead of
process groups, GSPMD/pjit + explicit ``shard_map`` collectives instead of
hand-issued ``xm.*`` ops, ``lax.ppermute`` pipeline p2p, Pallas kernels for
flash attention, and optimizer-state sharding for ZeRO-1.
"""

from neuronx_distributed_tpu.parallel import mesh as parallel_state  # noqa: F401
from neuronx_distributed_tpu.parallel.mesh import (  # noqa: F401
    initialize_model_parallel,
    model_parallel_is_initialized,
    destroy_model_parallel,
)
from neuronx_distributed_tpu.parallel.distributed import (  # noqa: F401
    initialize_distributed,
    shard_host_batch,
)

# top-level API parity with the reference package root
# (src/neuronx_distributed/__init__.py:2-8 re-exports the checkpoint + trainer
# surface as `nxd.*`)
from neuronx_distributed_tpu.checkpoint import (  # noqa: F401
    finalize_checkpoint,
    has_checkpoint,
    latest_tag,
    load_checkpoint,
    save_checkpoint,
)
from neuronx_distributed_tpu.trainer import (  # noqa: F401
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)

__version__ = "0.1.0"
