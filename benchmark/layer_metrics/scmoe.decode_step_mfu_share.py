"""Share of the chip's memory peak the WHOLE live decode step of a
shortcut-connected block reaches, in percent: the bytes the step must move
(``opcount_scmoe.decode_step_bytes``: both attentions' and both dense MLPs'
weights and the router of every layer, the real experts the counters say were
READ, ``moe_experts_touched / moe_layer_steps``, the head, the cached latent of
the live rows' tokens in every sub-layer) over the HBM's rate, over the
measured device time of a live step (``decode_steps.traced_decode``). A pick
that fell on an identity expert moves no weight and counts none: the program's
``moe_experts_touched`` counts held real experts only. None without identity
experts in the configuration, the routing counters or a traced decode block."""

from benchmark import decode_steps, opcount_scmoe


def read(record):
    cfg = opcount_scmoe.scmoe_config(record)
    stats = record.get("engine_stats") or {}
    if cfg is None or not record.get("peaks") or not stats.get("moe_layer_steps"):
        return None
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    need = opcount_scmoe.decode_step_bytes(
        cfg, ran["context_tokens"], stats["moe_experts_touched"] / stats["moe_layer_steps"])
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / ran["step_s"]
