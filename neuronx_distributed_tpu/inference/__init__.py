"""Inference stack (reference ``trace/`` + ``examples/inference/modules``;
SURVEY §3.5): AOT builder with shape router, KV-cached CausalLM serving,
samplers, the continuous-batching engine (``engine.py``). The replay driver (``replay.py``: load
generator and serving reports) sits above the engine, the router and the
disaggregated fleet and is loaded only when one of its names is asked for."""

from neuronx_distributed_tpu.inference.adapters import (  # noqa: F401
    AdapterLoadError,
    AdapterPool,
    AdapterPoolExhausted,
)
from neuronx_distributed_tpu.inference.autoscale import (  # noqa: F401
    AutoscalePolicy,
    Autoscaler,
)
from neuronx_distributed_tpu.inference.causal_lm import CausalLM, GenerationResult  # noqa: F401
from neuronx_distributed_tpu.inference.engine import (  # noqa: F401
    Completion,
    Rejected,
    ReplicaLoad,
    Request,
    ServeEngine,
)
from neuronx_distributed_tpu.inference.schedq import (  # noqa: F401
    AdmissionQueue,
    PendingQueue,
)
from neuronx_distributed_tpu.inference.simlm import SimCausalLM  # noqa: F401
from neuronx_distributed_tpu.inference.grammar import (  # noqa: F401
    CompiledGrammar,
    GrammarCompileError,
    GrammarLoadError,
    GrammarPool,
    GrammarPoolExhausted,
    compile_token_dfa,
    default_token_table,
    detokenize,
    json_schema_to_regex,
)
from neuronx_distributed_tpu.inference.faults import (  # noqa: F401
    DispatchFailed,
    FaultInjector,
    FaultPlan,
    TransientDispatchError,
)
from neuronx_distributed_tpu.inference.router import (  # noqa: F401
    NoLiveReplicas,
    Router,
)
from neuronx_distributed_tpu.inference.disagg import (  # noqa: F401
    DisaggRouter,
    KVHandoff,
)
from neuronx_distributed_tpu.inference.model_builder import ModelBuilder, NxDModel  # noqa: F401
from neuronx_distributed_tpu.inference.paged_cache import (  # noqa: F401
    PageAllocator,
    PagedKVCache,
    PagePoolExhausted,
    RadixPrefixIndex,
)
from neuronx_distributed_tpu.inference.sampling import Sampler, SlotSampler  # noqa: F401

_REPLAY_NAMES = ("run_trace", "synthetic_trace", "synthetic_trace_stream",
                 "run_router_trace", "run_disagg_trace")


def __getattr__(name):
    if name in _REPLAY_NAMES:
        from neuronx_distributed_tpu.inference import replay
        return getattr(replay, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
