"""Long-sequence validation gate (reference
``test/integration/llama2_7B/test_long_seqlen.py:83-95`` — compiles+runs
Llama-7B at seq 8k/16k/32k and asserts device-memory ceilings and minimum
throughput).

Hardware tier (SURVEY §4.2 tier c): runs on a real TPU chip. The reference's
thresholds are for 32 NeuronCores; here they are scaled per-chip:
8k: 54k/32 = 1687.5 tok/s/core, 16k: 42.6k/32 = 1331, 32k: 32.8k/32 = 1024
(each with the reference's 8% tolerance). Depth is reduced to 2 layers and
projected to 32 with the same step_time(L) = a + b*L fit bench.py uses (a
full 7B + optimizer does not fit one chip's HBM).

Exit code 0 iff every seq length passes. ``--smoke`` runs tiny dims on the
virtual CPU mesh (CI wiring check only, no thresholds).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
# light import: cp_microbench defers jax/package imports into the function
from neuronx_distributed_tpu.utils.cp_microbench import (
    measure_cp_ratio,
    measure_cp_ratio_isolated,
)

# (seq, min tokens/s/chip with 8% tolerance applied). The memory gate is
# execution itself: the timed steps RUN on the chip, so an OOM config fails
# loudly; compiled temp+argument bytes are recorded for trend tracking (the
# analysis double-counts donated buffers, so it is not a ceiling check).
THRESHOLDS = [
    (8192, 1687.5 * 0.92),
    (16384, 1331.0 * 0.92),
    (32768, 1024.0 * 0.92),
]
FULL_LAYERS = 32


def measure(seq: int, batch: int, tiny: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from bench import build_step, step_memory_bytes, timed_steps

    times = {}
    mem = None
    # 32k: selective "attention" remat's saved MLP intermediates no longer
    # fit one chip — full remat trades the FLOPs back (the reference makes
    # the same selective->full shift as seq grows, run_llama_nxd.py:113-114)
    remat = "attention" if seq <= 16384 else "full"
    for layers in (1, 2):
        step, state, batch_data, lcfg = build_step(layers, batch, seq, not tiny,
                                                   remat_policy=remat)
        if layers == 2:
            mem = step_memory_bytes(step, state, batch_data)
        dt, _ = timed_steps(step, state, batch_data, steps=2, windows=2)
        times[layers] = dt
        del step, state, batch_data
    b = times[2] - times[1]
    a = times[1] - b
    if b <= 0 or a < 0:
        a, b = 0.0, times[2] / 2
    tok_s = batch * seq / (a + FULL_LAYERS * b)
    return tok_s, mem


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny dims on the virtual CPU mesh (wiring check)")
    p.add_argument("--seqs", type=int, nargs="*", default=None)
    p.add_argument("--cp", action="store_true",
                   help="also run the CP-vs-SP attention microbench row")
    args = p.parse_args(argv)
    if args.smoke:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
        tok_s, mem = measure(512, 1, tiny=True)
        print(json.dumps({"smoke": True, "seq": 512, "tokens_per_sec": round(tok_s, 1)}))
        if args.cp:  # wiring check for the CP row (interpreted kernels, tiny;
            # allocs=1 — the HBM-placement protocol is meaningless on CPU)
            row = measure_cp_ratio(512, heads=4, head_dim=32, trials=1, allocs=1)
            row["smoke"] = True
            print(json.dumps(row))
        return 0

    import jax

    if jax.default_backend() != "tpu":
        print("long-seq validation needs a TPU chip (use --smoke on CPU)", file=sys.stderr)
        return 2
    ok = True
    for seq, min_tok_s in THRESHOLDS:
        if args.seqs and seq not in args.seqs:
            continue
        # batch chosen so tokens/step stays ~16k like the 8k reference config
        batch = max(1, 16384 // seq)
        t0 = time.time()
        tok_s, mem = measure(seq, batch, tiny=False)
        passed = tok_s >= min_tok_s
        ok &= passed
        print(json.dumps({
            "seq": seq, "batch": batch,
            "tokens_per_sec_per_chip_projected_32L": round(tok_s, 1),
            "min_required": round(min_tok_s, 1),
            "step_memory_bytes_2L": mem,
            "passed": passed,
            "wall_s": round(time.time() - t0, 1),
        }))
    if args.cp:
        for seq in (args.seqs or [16384]):
            # measured in this process (the chip belongs to one): the CP
            # kernel's runtime is HBM-placement sensitive (PROFILE.md r5 CP
            # note) and measure_cp_ratio re-rolls operand allocations
            row = measure_cp_ratio_isolated(seq)
            row["passed"] = passed_cp = row["cp_vs_sp_throughput"] >= 0.7
            ok &= passed_cp
            print(json.dumps(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
