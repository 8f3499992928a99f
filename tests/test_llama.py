"""Llama family tests: tiny configs on the 8-device CPU mesh.

Golden methodology as the reference (SURVEY §4.2): TP-sharded model output ==
dense single-device output; plus an end-to-end train-step smoke with
TP×DP×ZeRO-1 and SP on/off parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)

TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=2, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)


def _ids(shape, key=0):
    return jax.random.randint(jax.random.PRNGKey(key), shape, 0, 255)


def test_forward_tp_matches_dense():
    ids = _ids((2, 16))
    cfg = LlamaConfig(**TINY)
    model = LlamaForCausalLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), ids)

    from flax.core import meta
    dense_params = meta.unbox(variables)
    logits_dense = model.apply(dense_params, ids)

    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree
    sharded = jax.device_put(dense_params, named_sharding_tree(variables, st.mesh))
    with jax.set_mesh(st.mesh):
        logits_tp = jax.jit(model.apply)(sharded, ids)
    np.testing.assert_allclose(
        np.asarray(logits_tp), np.asarray(logits_dense), rtol=2e-4, atol=2e-4
    )


def test_sp_matches_non_sp():
    ids = _ids((2, 16), 1)
    cfg = LlamaConfig(**TINY)
    cfg_sp = LlamaConfig(**{**TINY, "sequence_parallel": True})
    model, model_sp = LlamaForCausalLM(cfg), LlamaForCausalLM(cfg_sp)
    variables = model.init(jax.random.PRNGKey(0), ids)
    from flax.core import meta
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree
    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    params = jax.device_put(meta.unbox(variables), named_sharding_tree(variables, st.mesh))
    with jax.set_mesh(st.mesh):
        out = jax.jit(model.apply)(params, ids)
        out_sp = jax.jit(model_sp.apply)(params, ids)
    np.testing.assert_allclose(np.asarray(out_sp), np.asarray(out), rtol=2e-4, atol=2e-4)


def test_flash_attention_path_matches_reference_path():
    ids = _ids((2, 64), 2)  # seq 64 ≥ one flash block
    cfg_ref = LlamaConfig(**TINY)
    cfg_flash = LlamaConfig(**{**TINY, "use_flash_attention": True,
                               "attention_block_q": 64, "attention_block_k": 64})
    model_ref, model_flash = LlamaForCausalLM(cfg_ref), LlamaForCausalLM(cfg_flash)
    variables = model_ref.init(jax.random.PRNGKey(0), ids)
    from flax.core import meta
    params = meta.unbox(variables)
    out_ref = model_ref.apply(params, ids)
    out_flash = model_flash.apply(params, ids)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref), rtol=2e-3, atol=2e-3)


def test_train_step_tp_dp_zero1():
    cfg = neuronx_distributed_config(
        tensor_parallel_size=2,
        optimizer_config={"zero_one_enabled": True},
        mixed_precision_config={"use_master_weights": True},
    )
    lcfg = LlamaConfig(**{**TINY, "remat_policy": "full"})
    ids = _ids((4, 16), 3)
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-3, weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(params, batch, rng):
        return model.module.apply({"params": params}, batch["ids"], batch["labels"],
                                  method=LlamaForCausalLM.loss)

    step = make_train_step(model, opt, loss_fn)
    batch = {"ids": np.asarray(ids), "labels": np.asarray(_ids((4, 16), 4))}
    losses = []
    for i in range(3):
        state, metrics = step(state, batch, jax.random.key(i))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_tied_embeddings():
    ids = _ids((2, 16), 5)
    cfg = LlamaConfig(**{**TINY, "tie_word_embeddings": True})
    model = LlamaForCausalLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), ids)
    from flax.core import meta
    from neuronx_distributed_tpu.models.llama import LlamaModel
    params = meta.unbox(variables)["params"]
    assert "lm_head" not in params, "tied model must not create a separate lm_head"
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # value check: logits == final_hidden @ E.T with the embedding table
    hidden = LlamaModel(cfg).apply({"params": params["model"]}, ids)
    table = params["model"]["embed"]["embedding"]
    expected = np.asarray(hidden, np.float32) @ np.asarray(table, np.float32).T
    np.testing.assert_allclose(np.asarray(logits), expected, rtol=1e-4, atol=1e-4)


def test_tp_flash_shard_map_path():
    """The mesh-initialized flash path (shard_map over dp×tp with the Pallas
    kernel) must match the dense no-flash golden — covers spec correctness,
    per-shard GQA head alignment, and check_vma handling."""
    ids = _ids((2, 64), 6)
    cfg_dense = LlamaConfig(**TINY)
    cfg_flash = LlamaConfig(**{**TINY, "use_flash_attention": True,
                               "attention_block_q": 32, "attention_block_k": 32})
    model_dense = LlamaForCausalLM(cfg_dense)
    model_flash = LlamaForCausalLM(cfg_flash)
    variables = model_dense.init(jax.random.PRNGKey(0), ids)
    from flax.core import meta
    dense_params = meta.unbox(variables)
    golden = model_dense.apply(dense_params, ids)

    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree
    sharded = jax.device_put(dense_params, named_sharding_tree(variables, st.mesh))
    with jax.set_mesh(st.mesh):
        out = jax.jit(model_flash.apply)(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-3, atol=2e-3)


def test_chunked_loss_matches_plain_exactly():
    """Long-seq CE chunking (head matmul + CE per sequence chunk under
    remat) must match the whole-sequence loss in value AND grads — the 32k
    memory lever cannot change numerics."""
    cfg_kw = {**TINY, "max_seq_len": 64, "remat_policy": None}
    ids = _ids((2, 64), 7)
    labels = np.array(_ids((2, 64), 8))
    labels[:, :5] = -100
    labels = jnp.asarray(labels)
    m_plain = LlamaForCausalLM(LlamaConfig(**{**cfg_kw, "loss_chunk_size": 9999}))
    m_chunk = LlamaForCausalLM(LlamaConfig(**{**cfg_kw, "loss_chunk_size": 16}))
    from flax.core import meta

    params = meta.unbox(m_plain.init(jax.random.PRNGKey(0), ids))

    def loss(m, p):
        return m.apply(p, ids, labels, method=LlamaForCausalLM.loss,
                       ignore_index=-100)

    np.testing.assert_allclose(float(loss(m_chunk, params)),
                               float(loss(m_plain, params)), rtol=1e-6)
    g1 = jax.grad(lambda p: loss(m_plain, p))(params)
    g2 = jax.grad(lambda p: loss(m_chunk, p))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6), g1, g2)


def test_context_parallel_matches_dense():
    """Ring-attention CP (cp=2 x tp=2): logits and loss match the dense
    single-device golden — the sequence never gathers through attention."""
    ids = _ids((2, 64), 9)
    labels = _ids((2, 64), 10)
    cfg_dense = LlamaConfig(**{**TINY, "max_seq_len": 64})
    cfg_cp = LlamaConfig(**{**TINY, "max_seq_len": 64, "context_parallel": True})
    model_d, model_cp = LlamaForCausalLM(cfg_dense), LlamaForCausalLM(cfg_cp)
    variables = model_d.init(jax.random.PRNGKey(0), ids)
    from flax.core import meta

    dense = meta.unbox(variables)
    golden = model_d.apply(dense, ids)
    golden_loss = model_d.apply(dense, ids, labels, method=LlamaForCausalLM.loss)

    st = ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                      context_parallel_size=2)
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree

    sharded = jax.device_put(dense, named_sharding_tree(variables, st.mesh))
    with jax.set_mesh(st.mesh):
        out = jax.jit(model_cp.apply)(sharded, ids)
        loss = jax.jit(
            lambda p: model_cp.apply(p, ids, labels, method=LlamaForCausalLM.loss)
        )(sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(loss), float(golden_loss), rtol=1e-5)


def test_context_parallel_zigzag_matches_dense():
    """Zigzag CP: feeding zigzag-permuted (ids, labels) with
    cp_layout='zigzag' reproduces the dense loss — RoPE positions, the
    ring's causal mask, and the CE pairing all follow the permutation."""
    from neuronx_distributed_tpu.ops.ring_attention import zigzag_indices

    ids = _ids((2, 64), 13)
    labels = _ids((2, 64), 14)
    cfg_dense = LlamaConfig(**{**TINY, "max_seq_len": 64})
    model_d = LlamaForCausalLM(cfg_dense)
    variables = model_d.init(jax.random.PRNGKey(0), ids)
    from flax.core import meta

    dense = meta.unbox(variables)
    golden_loss = model_d.apply(dense, ids, labels, method=LlamaForCausalLM.loss)
    golden_logits = model_d.apply(dense, ids)

    st = ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                      context_parallel_size=2)
    idx = zigzag_indices(64, 2)
    cfg_cp = LlamaConfig(**{**TINY, "max_seq_len": 64, "context_parallel": True,
                            "cp_layout": "zigzag"})
    model_cp = LlamaForCausalLM(cfg_cp)
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree

    sharded = jax.device_put(dense, named_sharding_tree(variables, st.mesh))
    with jax.set_mesh(st.mesh):
        loss = jax.jit(
            lambda p: model_cp.apply(p, ids[:, idx], labels[:, idx],
                                     method=LlamaForCausalLM.loss)
        )(sharded)
        logits = jax.jit(model_cp.apply)(sharded, ids[:, idx])
    np.testing.assert_allclose(float(loss), float(golden_loss), rtol=1e-5)
    # un-permuting the output recovers the dense logits
    inv = np.argsort(np.asarray(idx))
    np.testing.assert_allclose(np.asarray(logits)[:, inv],
                               np.asarray(golden_logits), rtol=2e-4, atol=2e-4)


def test_context_parallel_train_step():
    cfg = neuronx_distributed_config(
        tensor_parallel_size=2,
        optimizer_config={"zero_one_enabled": True},
    )
    ps.destroy_model_parallel()
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 context_parallel_size=2)
    lcfg = LlamaConfig(**{**TINY, "max_seq_len": 64, "context_parallel": True})
    ids = _ids((4, 64), 11)
    labels = _ids((4, 64), 12)
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=3e-3,
                                        weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(params, batch, rng):
        return model.module.apply({"params": params}, batch["ids"],
                                  batch["labels"], method=LlamaForCausalLM.loss)

    step = make_train_step(model, opt, loss_fn)
    losses = []
    for i in range(3):
        state, m = step(state, {"ids": np.asarray(ids),
                                "labels": np.asarray(labels)}, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_cp_config_propagates_to_model():
    """neuronx_distributed_config(context_parallel_size=2) alone must turn on
    the model's ring-attention path — a cp mesh axis with CP off would
    silently replicate the forward (r2 review)."""
    cfg = neuronx_distributed_config(tensor_parallel_size=2,
                                     context_parallel_size=2)
    lcfg = LlamaConfig(**{**TINY, "max_seq_len": 64})
    assert not lcfg.context_parallel
    ids = _ids((2, 64), 13)
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    assert model.module.config.context_parallel
    assert model.mesh.shape["cp"] == 2


def test_model_presets_are_consistent():
    """Every published preset must be internally consistent: heads divide
    hidden, kv heads divide heads (GQA), and the flagship dims match the
    published architectures (reference workloads: llama2 7B/13B/70B,
    llama3 8B/70B, llama3.1 8B)."""
    from neuronx_distributed_tpu.models.llama import (
        llama2_7b, llama2_13b, llama2_70b, llama3_8b, llama31_8b, llama3_70b)

    # (hidden, inter, layers, heads, kv, vocab, max_seq) per published arch
    presets = {
        "llama2_7b": (llama2_7b(), 4096, 11008, 32, 32, 32, 32000, 4096),
        "llama2_13b": (llama2_13b(), 5120, 13824, 40, 40, 40, 32000, 4096),
        "llama2_70b": (llama2_70b(), 8192, 28672, 80, 64, 8, 32000, 4096),
        "llama3_8b": (llama3_8b(), 4096, 14336, 32, 32, 8, 128256, 8192),
        "llama31_8b": (llama31_8b(), 4096, 14336, 32, 32, 8, 128256, 131072),
        "llama3_70b": (llama3_70b(), 8192, 28672, 80, 64, 8, 128256, 8192),
    }
    for name, (cfg, hidden, inter, layers, heads, kv, vocab, mx) in presets.items():
        assert cfg.hidden_size == hidden, name
        assert cfg.intermediate_size == inter, name
        assert cfg.num_layers == layers, name
        assert cfg.num_heads == heads and cfg.num_kv_heads == kv, name
        assert cfg.vocab_size == vocab, name
        assert cfg.max_seq_len == mx, name
        assert cfg.hidden_size % cfg.num_heads == 0, name
        assert cfg.num_heads % cfg.num_kv_heads == 0, name
    # llama3 family uses the 500k rope base; llama3.1 adds the NTK scaling
    assert llama3_70b().rope_theta == 500000.0
    assert llama31_8b().rope_scaling is not None
    assert llama3_8b().rope_scaling is None
