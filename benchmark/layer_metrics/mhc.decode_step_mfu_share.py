"""Share of the chip's memory peak the WHOLE live decode step of a model that
carries several residual streams reaches, in percent: the bytes the step must
move (``opcount_mhc.decode_step_bytes``: MLA's five matrices and both mixes'
projections of every layer, the dense MLP, the shared expert, the router, the
experts the counters say were READ, the head, the cached latent of the live
rows' tokens) over the HBM's rate, over the measured device time of a live
step (``decode_steps.traced_decode``). The mixes' serial part (a Sinkhorn chain
a sub-block) moves nothing and is in the time alone. None without ``hc_mult``
in the configuration, the program's ``mhc_mix_steps`` counter, the routing
counters or a traced decode block."""

from benchmark import decode_steps, opcount_mhc


def read(record):
    cfg = opcount_mhc.mhc_config(record)
    stats = record.get("engine_stats") or {}
    if (cfg is None or not record.get("peaks") or not stats.get("moe_layer_steps")
            or not stats.get("mhc_mix_steps")):
        return None
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    need = opcount_mhc.decode_step_bytes(
        cfg, ran["rows"], ran["context_tokens"],
        stats["moe_experts_touched"] / stats["moe_layer_steps"])
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / ran["step_s"]
