"""OLMoE model family (``model_type: olmoe``; OLMoE-1B-7B): the Mixtral stack
with two published differences, both switches on code that is already there.

* QK-norm: an RMSNorm of the q and of the k projection over ALL heads
  together, before the split into heads and the rotary
  (``LlamaConfig.qk_norm`` -> ``LlamaAttention``).
* The router keeps the top-k softmax probabilities as they are
  (``norm_topk_prob: false``): top-8 of 64, summing to less than one
  (``MixtralConfig.norm_topk_prob`` -> ``RouterTopK``).

Embedding, rotary, layer scan, attention, paged cache, ``MoE`` and
``ExpertMLPs`` are Mixtral's; the expert dispatch is whatever ``MoE`` chooses
(``moe/layer.py``: serving reads the experts its real tokens chose, about a
third of the 64 at three live rows, through one grouped matmul).
"""

from __future__ import annotations

import dataclasses

from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(MixtralConfig):
    qk_norm: bool = True
    norm_topk_prob: bool = False
    num_experts: int = 64
    top_k: int = 8


def olmoe_1b_7b(**over) -> OlmoeConfig:
    """allenai/OLMoE-1B-7B-0125-Instruct: 6.92 B parameters, 1.3 B active."""
    return OlmoeConfig(**{**dict(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=16, num_heads=16, num_kv_heads=16, rope_theta=10000.0,
        rms_norm_eps=1e-5, max_seq_len=4096,
    ), **over})


class OlmoeForCausalLM(MixtralForCausalLM):
    """``MixtralForCausalLM`` (its decoder layer class) under OLMoE's name."""
