"""Operations and bytes a latent-attention (MLA) configuration NEEDS, from its
published sizes: DeepSeek-V2's layer, beside ``opcount.py`` (whose counts are
GQA's: q/k/v/o of equal head size and ``2 x kv heads x head_dim`` of cache).

Needed, not executed (``opcount.py``): a decode step needs each weight it
multiplies by once, the experts it READ, and the cached latent of its live
rows' tokens; not the gathered ``(rows, max_seq_len)`` slab, not padding.

``cfg`` is a configuration file's dict (``configs/<name>.json``) in the
published key names; ``n_routed_experts`` counts the experts HELD here (the
router is ``router_experts`` wide). One multiply-add is two operations.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """The five matrices of one layer's latent attention: q down and up, kv
    down (latent and rotary key), kv up (k_nope and v per head), o."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * q_rank + q_rank * n * (nope + rope) + h * (rank + rope)
            + rank * n * (nope + v) + n * v * h)


def mlp_params(cfg: dict, width: int) -> int:
    """One SwiGLU MLP (gate, up, down) of ``width``."""
    return 3 * cfg["hidden_size"] * width


def dense_mlp_params(cfg: dict) -> int:
    return mlp_params(cfg, cfg["intermediate_size"])


def expert_params(cfg: dict) -> int:
    return mlp_params(cfg, cfg["moe_intermediate_size"])


def shared_params(cfg: dict) -> int:
    return mlp_params(cfg, cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg.get("router_experts", cfg["n_routed_experts"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def layers(cfg: dict) -> tuple:
    """(leading dense layers, expert layers)."""
    dense = cfg.get("first_k_dense_replace", 0)
    return dense, cfg["num_hidden_layers"] - dense


def expert_layer_params(cfg: dict) -> int:
    """One expert layer AS HELD: attention, shared experts, the whole router
    and the ``n_routed_experts`` held."""
    return (attention_params(cfg) + shared_params(cfg) + router_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    return attention_params(cfg) + dense_mlp_params(cfg)


def total_params(cfg: dict) -> int:
    """Layers as held + embedding + untied head (norms left out)."""
    dense, expert = layers(cfg)
    return (dense * dense_layer_params(cfg) + expert * expert_layer_params(cfg)
            + 2 * head_params(cfg))


def latent_bytes_per_token_layer(cfg: dict, bytes_per_el: int = 2) -> int:
    """What one token leaves in one layer's cache: ``[c_kv | k_rope]``."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_el


def decode_step_bytes(cfg: dict, rows: float, context_tokens: float,
                      experts_read: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step over ``rows`` streams must read: every layer's
    attention matrices once, the dense layers' MLP, per expert layer the
    shared MLP, the router and the ``experts_read`` experts a live layer-step
    READ (``moe_experts_touched / moe_layer_steps``), the output head once,
    and the cached latent of ``context_tokens`` tokens (summed over the live
    rows) in every layer. ``rows`` is not in the count: a weight is read once
    whatever the rows (they are in ``experts_read`` and ``context_tokens``)."""
    dense, expert = layers(cfg)
    experts_read = min(float(experts_read), cfg["n_routed_experts"])
    weights = (cfg["num_hidden_layers"] * attention_params(cfg)
               + dense * dense_mlp_params(cfg)
               + expert * (shared_params(cfg) + router_params(cfg)
                           + experts_read * expert_params(cfg))
               + head_params(cfg))
    cache = context_tokens * cfg["num_hidden_layers"] * latent_bytes_per_token_layer(
        cfg, bytes_per_el)
    return weights * bytes_per_el + cache
