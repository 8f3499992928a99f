"""Llama-2 7B TP+ZeRO-1+SP pretraining.

TPU-native counterpart of the reference's
``examples/training/llama/tp_zero1_llama_hf_pretrain`` scripts
(``run_llama_nxd.py`` — TP8, ZeRO-1 sharded AdamW with fp32 masters,
sequence parallelism, selective activation checkpointing, flash attention).

Run (full scale; TP defaults to every attached device):
    python examples/training/llama2_tp_zero1.py --tp 8 --steps 100
One 16 GB chip (7B widths, depth cut to fit):
    python examples/training/llama2_tp_zero1.py --num_layers 2 --batch_size 8 --seq_len 2048 --steps 3
CI smoke:
    python examples/training/llama2_tp_zero1.py --tiny --steps 4
Pod launch (reference ``run_llama2_70B_tp_pp.sh`` torchrun role — every host
runs the same command; see ``scripts/launch_pod.sh``):
    # on host i of N:
    python examples/training/llama2_tp_zero1.py --tp 8 --steps 100 \
        --coordinator_address host0:8476 --num_processes N --process_id i
``--batch_size`` is the GLOBAL batch; each host feeds batch/N rows
(TokenShardDataset rank/world sharding, or the synthetic slice).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax.numpy as jnp

from common import (
    make_lr,
    add_common_args,
    distribute_batches,
    maybe_resume,
    setup_example,
    synthetic_lm_batches,
    train_loop,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM, llama2_7b
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)


def build_config(args, seq: int) -> LlamaConfig:
    if args.tiny:
        return LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=4, max_seq_len=seq, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None,
        )
    # bf16 storage + fp32 masters in the ZeRO-1 optimizer; "attention" remat
    # is the reference's selective-checkpoint choice (run_llama_nxd.py:113).
    # --num_layers cuts DEPTH to what the attached chips hold; widths stay
    # the published 7B ones.
    depth = {"num_layers": args.num_layers} if args.num_layers else {}
    return llama2_7b(
        max_seq_len=seq, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        sequence_parallel=True, remat_policy="attention", **depth,
    )


def build_training(args, lcfg: LlamaConfig, tp: int, sample_ids, steps: int):
    """Model, ZeRO-1 optimizer and jitted step for ``lcfg`` at TP degree
    ``tp`` — the construction ``main`` runs and ``chip_smoke.py`` reuses.
    Returns ``(state, step)``."""
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=tp,
        sequence_parallel=lcfg.sequence_parallel,
        optimizer_config={"zero_one_enabled": True, "grad_clipping": True,
                          "max_grad_norm": 1.0},
        mixed_precision_config={"use_master_weights": True},
    )
    model = initialize_parallel_model(
        nxd_config, lambda: LlamaForCausalLM(lcfg), sample_ids
    )
    opt = initialize_parallel_optimizer(
        nxd_config, model, learning_rate=make_lr(args, steps), weight_decay=args.weight_decay
    )
    state = maybe_resume(args.checkpoint_dir, create_train_state(model, opt))

    def loss_fn(params, b, rng):
        return model.module.apply(
            {"params": params}, b["ids"], b["labels"], method=LlamaForCausalLM.loss
        )

    step = make_train_step(model, opt, loss_fn,
                           grad_accum_steps=args.grad_accum_usteps)
    return state, step


def main(argv=None) -> float:
    parser = add_common_args(argparse.ArgumentParser(description=__doc__))
    parser.add_argument("--shard_glob", type=str, default=None,
                        help="token-shard files (data.TokenShardDataset); "
                             "default: hermetic synthetic batches")
    parser.add_argument("--num_layers", type=int, default=None,
                        help="cut the 7B model's depth to what the chips "
                             "hold (default: all 32 layers)")
    args = parser.parse_args(argv)
    setup_example(args)
    import jax

    n_hosts = jax.process_count()
    tp = args.tensor_parallel_size or (2 if args.tiny else jax.device_count())
    batch = args.batch_size or (4 if args.tiny else 8)  # GLOBAL batch
    if batch % n_hosts:
        raise SystemExit(f"--batch_size {batch} not divisible by {n_hosts} hosts")
    local_batch = batch // n_hosts
    seq = args.seq_len or (32 if args.tiny else 4096)
    steps = args.steps or (4 if args.tiny else 100)
    if args.shard_glob:
        import glob as _glob

        from neuronx_distributed_tpu.data import TokenShardDataset

        shard_paths = sorted(_glob.glob(args.shard_glob))
        ds = TokenShardDataset(shard_paths, batch_size=local_batch,
                               shuffle_seed=args.seed,
                               rank=jax.process_index(), world_size=n_hosts)
        seq = ds.seq_len  # the shards define the sequence length

    lcfg = build_config(args, seq)
    if args.shard_glob:
        batches = iter(ds)
    else:
        batches = distribute_batches(
            synthetic_lm_batches(lcfg.vocab_size, batch, seq, seed=args.seed), batch)
    sample = next(batches)
    state, step = build_training(args, lcfg, tp, sample["ids"], steps)
    state, metrics = train_loop(
        step, state, batches, steps,
        batch_size=batch, log_every=args.log_every,
        checkpoint_dir=args.checkpoint_dir, checkpoint_every=args.checkpoint_every,
        metrics_file=args.metrics_file, profile_dir=args.profile_dir, seed=args.seed,
        trace_out=args.trace_out, metrics_out=args.metrics_out,
    )
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
