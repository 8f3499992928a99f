"""Attention dispatch: Pallas flash kernel under the right parallelism.

The reference wires its NKI flash kernel straight into model code
(``examples/training/llama/modeling_llama_nxd.py:340``, prefill gating
``examples/inference/modules/attention/attention_base.py:103-114``). Here the
model calls :func:`attention`, which

* runs the Pallas kernel inside a ``shard_map`` over the global mesh when
  parallel state is initialized — batch over the DP axes, heads over TP, so
  the kernel works on local shards and no collective touches the seq dim
  (TP attention: heads are embarrassingly parallel);
* falls back to a direct kernel call when no mesh is initialized
  (single-device tests), and to the plain-XLA reference path when
  ``use_flash=False`` (short sequences, exotic masks).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from neuronx_distributed_tpu.kernels.flash_attn import flash_attention, reference_attention
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.parallel.mesh import DP_AXES, TP_AXIS


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    use_flash: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-head attention over BHSD tensors; K/V may carry fewer (GQA)
    heads. Heads must be TP-sharded (the GQA QKV layer's output layout).

    ``q_positions``/``kv_positions`` ((b, sq)/(b, sk) int32) select the
    position-based mask (padded prompts, KV-cache decode — see
    kernels/flash_attn.py); defaults are (bottom-aligned) causal. ``window``
    (forward only): a key must also lie within ``window`` positions of its
    query; None passes nothing on and is the call it always was."""
    windowed = {} if window is None else {"window": window}
    if not use_flash:
        return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   q_positions=q_positions, kv_positions=kv_positions,
                                   **windowed)
    if not ps.model_parallel_is_initialized():
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               q_positions=q_positions, kv_positions=kv_positions,
                               **windowed)
    mesh = ps.get_mesh()
    spec = P(DP_AXES, TP_AXIS, None, None)
    pos_spec = P(DP_AXES, None)  # positions are per-batch, replicated over TP
    from neuronx_distributed_tpu.kernels.flash_attn import resolve_positions

    q_positions, kv_positions = resolve_positions(
        q.shape[0], q.shape[2], k.shape[2], causal, q_positions, kv_positions
    )

    def call(q, k, v, qp, kp):
        return flash_attention(q, k, v, sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k, q_positions=qp, kv_positions=kp,
                               **windowed)

    # check_vma=False: pallas_call out_shapes don't carry vma annotations
    return shard_map(
        call, mesh=mesh, in_specs=(spec, spec, spec, pos_spec, pos_spec),
        out_specs=spec, check_vma=False,
    )(q, k, v, q_positions, kv_positions)
