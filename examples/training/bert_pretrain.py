"""BERT-large TP+DP MLM/NSP pretraining.

TPU-native counterpart of the reference's
``examples/training/tp_dp_bert_large_hf_pretrain_hdf5.py`` (846 LoC): the
module-surgery that swapped HF attention for ``ParallelSelfAttention``/
``ParallelSelfOutput`` (:344-383) is unnecessary — ``models/bert.py`` is
TP-sharded natively — and the HDF5 loader is replaced by hermetic synthetic
MLM batches (same five record fields).

Run (full scale, v5e-8-class slice):
    python examples/training/bert_pretrain.py --tp 8 --steps 1000
CI smoke (8-device CPU mesh):
    python examples/training/bert_pretrain.py --tiny --steps 4
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
    synthetic_mlm_batches,
    train_loop,
)
from neuronx_distributed_tpu.models.bert import BertConfig, BertForPreTraining, bert_large
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)


def build_config(args) -> BertConfig:
    if args.tiny:
        return BertConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, max_position_embeddings=128, dtype=jnp.float32,
            use_flash_attention=False,
        )
    return bert_large()


def main(argv=None) -> float:
    parser = add_common_args(argparse.ArgumentParser(description=__doc__))
    args = parser.parse_args(argv)
    setup_example(args)
    tp = args.tensor_parallel_size or (2 if args.tiny else 8)
    batch = args.batch_size or (4 if args.tiny else 16)
    seq = args.seq_len or (32 if args.tiny else 512)
    steps = args.steps or (4 if args.tiny else 1000)

    bcfg = build_config(args)
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=tp,
        optimizer_config={"zero_one_enabled": True},
        mixed_precision_config={"use_master_weights": True},
    )
    batches = distribute_batches(
        synthetic_mlm_batches(bcfg.vocab_size, batch, seq, seed=args.seed), batch)
    sample = next(batches)
    model = initialize_parallel_model(
        nxd_config, lambda: BertForPreTraining(bcfg), sample["input_ids"]
    )
    opt = initialize_parallel_optimizer(
        nxd_config, model, learning_rate=make_lr(args, steps), weight_decay=args.weight_decay
    )
    state = maybe_resume(args.checkpoint_dir, create_train_state(model, opt))

    def loss_fn(params, b, rng):
        return model.module.apply(
            {"params": params}, b["input_ids"], b["masked_lm_labels"],
            b["next_sentence_labels"], b["token_type_ids"], b["attention_mask"],
            method=BertForPreTraining.loss,
            deterministic=False, rngs={"dropout": rng},
        )

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
