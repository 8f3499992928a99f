"""Share of the chip's memory peak the WHOLE live decode step of a model with
a lightning indexer reaches, in percent: the bytes the step must move
(``opcount_sparse.decode_step_bytes``: MLA's five and the indexer's three
matrices of every layer, the dense MLP, the shared expert, the router, the
experts the counters say were READ, the head, the index key of every token
visible to a live row and the latent of every CHOSEN one) over the HBM's rate,
over the measured device time of a live step (``decode_steps.traced_decode``).
The chosen share of the traced steps' context is the window's
``dsa_tokens_selected / dsa_tokens_visible``. A step that reads its rows'
whole extent under a mask moves more than is counted, and reads lower for it.
None without an indexer in the configuration, its counters, the routing
counters or a traced decode block."""

from benchmark import decode_steps, opcount_sparse


def read(record):
    cfg = opcount_sparse.sparse_config(record)
    stats = record.get("engine_stats") or {}
    if (cfg is None or not record.get("peaks") or not stats.get("moe_layer_steps")
            or not stats.get("dsa_tokens_visible")):
        return None
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    visible = ran["context_tokens"]
    need = opcount_sparse.decode_step_bytes(
        cfg, visible, visible * stats["dsa_tokens_selected"] / stats["dsa_tokens_visible"],
        stats["moe_experts_touched"] / stats["moe_layer_steps"])
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / ran["step_s"]
