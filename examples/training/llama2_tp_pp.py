"""Llama-2 70B TP×PP pretraining.

TPU-native counterpart of the reference's
``examples/training/llama/tp_pp_llama_hf_pretrain/run_llama2_70B_tp_pp.sh``
(TP8 × PP8, 1F1B microbatching, GQA, ZeRO-1). The reference FX-traces and
splits the HF module graph (``NxDPPModel``, SURVEY §3.3); here the stage
partition is an array sharding — the scan-stacked layer params' leading axis
is sharded over the ``pp`` mesh axis and the engine runs collective-permute
microbatch shifts (``pipeline/engine.py``).

Run (full scale, TP8×PP8 = 64 chips):
    python examples/training/llama2_tp_pp.py --tp 8 --pp 8 --steps 30
CI smoke (PP2×TP2 on the 8-device CPU mesh):
    python examples/training/llama2_tp_pp.py --tiny --steps 3
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
from neuronx_distributed_tpu.models.llama import LlamaConfig, llama2_70b
from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)


def build_config(args, seq: int) -> LlamaConfig:
    if args.tiny:
        return LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=4,
            num_heads=4, num_kv_heads=2, kv_size_multiplier=2, max_seq_len=seq,
            dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
        )
    return llama2_70b(
        max_seq_len=seq, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat_policy="full",
    )


def main(argv=None) -> float:
    parser = add_common_args(argparse.ArgumentParser(description=__doc__))
    parser.add_argument("--num_microbatches", type=int, default=None)
    parser.add_argument("--num_chunks", type=int, default=1,
                        help="virtual-pipeline (interleaved) chunks per stage")
    args = parser.parse_args(argv)
    setup_example(args)
    tp = args.tensor_parallel_size or (2 if args.tiny else 8)
    pp = args.pipeline_parallel_size or (2 if args.tiny else 8)
    batch = args.batch_size or (4 if args.tiny else 32)
    seq = args.seq_len or (32 if args.tiny else 4096)
    steps = args.steps or (3 if args.tiny else 30)
    num_mb = args.num_microbatches or (2 if args.tiny else 8)

    lcfg = build_config(args, seq)
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=tp,
        pipeline_parallel_size=pp,
        pipeline_config={"num_microbatches": num_mb},
        optimizer_config={"zero_one_enabled": True},
        mixed_precision_config={"use_master_weights": True},
    )
    if not ps.model_parallel_is_initialized():
        ps.initialize_model_parallel(
            tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp
        )
    batches = distribute_batches(
        synthetic_lm_batches(lcfg.vocab_size, batch, seq, seed=args.seed), batch)
    sample = next(batches)
    pmodel = PipelinedLlama(lcfg, num_stages=pp, num_microbatches=num_mb,
                            num_chunks=args.num_chunks)
    model = pmodel.as_parallel_model(jnp.asarray(sample["ids"]), seed=args.seed)
    opt = initialize_parallel_optimizer(
        nxd_config, model, learning_rate=make_lr(args, steps), weight_decay=args.weight_decay
    )
    state = maybe_resume(args.checkpoint_dir, create_train_state(model, opt))

    def loss_fn(params, b, rng):
        return pmodel.loss(params, b["ids"], b["labels"])

    step = make_train_step(model, opt, loss_fn,
                           grad_accum_steps=args.grad_accum_usteps)
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
