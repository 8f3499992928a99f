"""Share of its memory roofline the decode step of a latent-attention (MLA)
configuration reaches, in percent: the bytes a live step NEEDS
(``opcount_latent.decode_step_bytes``: the five attention matrices of every
layer, the dense layer's MLP, per expert layer the shared MLP, the router and
the experts the step READ, the head, and 2 x (kv_lora_rank + qk_rope_head_dim)
bytes a cached token of the live rows a layer) over the chip's HBM bandwidth,
over the measured device time of a live step (``decode.step_ms``). The whole
step's share, so it cannot pass 100 % unless the count is wrong. Rows, context
and live steps are ``decode_steps.traced_decode``'s; the experts read a live
layer-step are this reader's own, ``moe_experts_touched / moe_layer_steps``
over the window (the program counts the expert layers only; ``traced_decode``
divides by every layer). None for a configuration without a latent cache, for
a program without the counters, or without a traced decode block."""

from benchmark import decode_steps, opcount_latent


def read(record):
    cfg = record.get("config") or {}
    stats = record.get("engine_stats") or {}
    if "kv_lora_rank" not in cfg or not record.get("peaks") or not stats.get("moe_layer_steps"):
        return None
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    experts_read = stats["moe_experts_touched"] / stats["moe_layer_steps"]
    need = opcount_latent.decode_step_bytes(cfg, ran["rows"], ran["context_tokens"], experts_read)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / ran["step_s"]
