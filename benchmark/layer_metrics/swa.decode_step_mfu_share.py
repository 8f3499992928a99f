"""Share of the chip's peak the WHOLE live decode step reaches, in percent:
the least time the step's needed bytes and operations can take
(``opcount_window.decode_step_roofline_s``: every weight it multiplies once,
the experts READ from ``moe_experts_touched``, the head, the live rows' cached
tokens as far as they reach in the full layers and ``min(reach, window)`` in
the window layers; the larger of bytes / HBM rate and FLOPs / bf16 peak) over
the measured device time of a live step (``decode_steps.traced_decode``). None
without window layers, the routing counters or a traced decode block."""

from benchmark import decode_steps, opcount_window


def read(record):
    cfg = opcount_window.window_config(record)
    stats = record.get("engine_stats") or {}
    if cfg is None or not stats.get("moe_layer_steps") or not stats.get("kv_walk_steps"):
        return None
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    lo, hi = record["traced"]
    inside = [rows for stamp, rows in decode_steps.blocks_by_stamp(record["rows"]).items()
              if lo < stamp <= hi]
    window_tokens = opcount_window.window_tokens_of(inside, cfg["sliding_window"]) / ran["live_steps"]
    least = opcount_window.decode_step_roofline_s(
        cfg, ran["rows"], ran["context_tokens"], window_tokens,
        stats["moe_experts_touched"] / stats["moe_layer_steps"],
        stats["moe_assignments"] / stats["kv_walk_steps"], record["peaks"])
    return 100.0 * least / ran["step_s"]
