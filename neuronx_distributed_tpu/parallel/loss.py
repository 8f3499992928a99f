"""Vocab-parallel cross-entropy (reference
``parallel_layers/loss_functions.py`` — ``_ParallelCrossEntropy``:11,
``parallel_cross_entropy``:133).

The reference computes a numerically-stable CE over vocab-sharded logits with
two explicit TP all-reduces (max, sum-exp) and XLA-friendly mul-masking
instead of boolean indexing. Under GSPMD the same algorithm is written as
plain jnp reductions over the (sharded) vocab axis — XLA emits the same two
all-reduces — and the mul-masking trick is kept (one-hot matmul instead of
gather) so the op partitions cleanly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def parallel_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
    ignore_index: Optional[int] = None,
) -> jax.Array:
    """Per-token cross entropy. ``logits``: (..., vocab) — may be vocab-sharded
    over TP under GSPMD; ``labels``: (...) int32. Returns per-token loss with
    ``ignore_index`` positions zeroed (mask by multiply, reference
    loss_functions.py:58-76)."""
    with jax.named_scope("loss"):
        vocab = logits.shape[-1]
        logits = logits.astype(jnp.float32)
        # stable logsumexp; the max/sum reductions over the sharded vocab axis
        # are where GSPMD inserts the two TP all-reduces of the reference
        # (:30-49)
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        shifted = logits - m
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + jnp.squeeze(m, -1)
        one_hot = jax.nn.one_hot(labels, vocab, dtype=logits.dtype)
        label_logit = jnp.sum(one_hot * logits, axis=-1)
        loss = lse - label_logit
        if label_smoothing > 0.0:
            # smoothed target: (1-eps) * one_hot + eps/vocab (reference :78-99)
            mean_logit = jnp.mean(logits, axis=-1)
            loss = ((1.0 - label_smoothing) * loss
                    + label_smoothing * (lse - mean_logit))
        if ignore_index is not None:
            mask = (labels != ignore_index).astype(loss.dtype)
            loss = loss * mask
        return loss


def parallel_cross_entropy_mean(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
    ignore_index: Optional[int] = None,
) -> jax.Array:
    """Mean loss over non-ignored tokens."""
    loss = parallel_cross_entropy(logits, labels, label_smoothing, ignore_index)
    with jax.named_scope("loss"):
        if ignore_index is None:
            return jnp.mean(loss)
        denom = jnp.maximum(jnp.sum((labels != ignore_index).astype(jnp.float32)), 1.0)
        return jnp.sum(loss) / denom
