"""MoE tests (reference device_correctness_test_runner methodology, SURVEY
§4.2): capacity-factor vs all-experts golden at high capacity, dropping
behavior, EP+TP sharded run vs dense golden, aux loss sanity, train smoke."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.moe import MoE, collect_aux_losses
from neuronx_distributed_tpu.moe.routing import RouterTopK, load_balancing_loss
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings


def _moe(mode, cf=8.0, **over):
    kw = dict(num_experts=4, hidden_size=32, intermediate_size=64, top_k=2,
              mode=mode, capacity_factor=cf, dtype=jnp.float32)
    kw.update(over)
    return MoE(**kw)


def test_router_topk_properties():
    r = RouterTopK(num_experts=8, top_k=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    (combine, logits), _ = r.init_with_output(jax.random.PRNGKey(1), x)
    nz = (np.asarray(combine) > 0).sum(axis=1)
    assert (nz == 2).all()
    np.testing.assert_allclose(np.asarray(combine).sum(axis=1), 1.0, rtol=1e-5)


def test_capacity_matches_all_experts_at_high_capacity():
    """With capacity >= T no token drops: capacity-factor == all-experts
    (the reference's CPU-golden equivalence, device_correctness_test_runner)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    m_cap = _moe("capacity_factor", cf=8.0)
    m_all = _moe("all_experts")
    vs = m_cap.init(jax.random.PRNGKey(1), x)
    out_cap, _ = m_cap.apply(vs, x, mutable=["losses"])
    out_all, _ = m_all.apply(vs, x, mutable=["losses"])
    np.testing.assert_allclose(np.asarray(out_cap), np.asarray(out_all), rtol=2e-4, atol=2e-4)


def test_capacity_drops_tokens():
    """With tiny capacity most tokens drop -> output far from all-experts,
    dropped tokens produce zeros."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 32))
    m_tiny = _moe("capacity_factor", cf=0.1)  # capacity = max(1, 3.2/4) -> ~0-1 per expert
    vs = m_tiny.init(jax.random.PRNGKey(1), x)
    out, _ = m_tiny.apply(vs, x, mutable=["losses"])
    # at least one token got fully dropped (all-zero output row)
    rows = np.abs(np.asarray(out)).sum(axis=-1).ravel()
    assert (rows == 0).any()


def test_aux_loss_sown_and_positive():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    m = _moe("capacity_factor")
    vs = m.init(jax.random.PRNGKey(1), x)
    out, mut = m.apply(vs, x, mutable=["losses"])
    aux = collect_aux_losses(mut)
    assert float(aux) > 0.0
    # balanced-ish random routing: aux close to coef * 1.0 (perfect balance = E*(1/E*1/E)*E = 1)
    assert float(aux) < 0.5


def test_ep_tp_sharded_matches_dense():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    m = _moe("capacity_factor", cf=8.0)
    vs = m.init(jax.random.PRNGKey(1), x)
    dense_params = meta.unbox(vs)
    golden, _ = m.apply(dense_params, x, mutable=["losses"])

    # ep=2, tp=2, edp=2 on 8 devices
    st = ps.initialize_model_parallel(tensor_model_parallel_size=2, expert_model_parallel_size=2)
    from flax import linen as nn
    shardings = specs_to_shardings(nn.get_partition_spec(vs), st.mesh)
    sharded = jax.device_put(dense_params, shardings)
    with jax.set_mesh(st.mesh):
        out, _ = jax.jit(lambda p, x: m.apply(p, x, mutable=["losses"]))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-4, atol=2e-4)


def test_moe_train_step_decreases_loss():
    """MoE + EP + ZeRO-1 through the full trainer."""
    from flax import linen as nn
    from neuronx_distributed_tpu.trainer import (
        create_train_state, initialize_parallel_model,
        initialize_parallel_optimizer, make_train_step, neuronx_distributed_config,
    )

    class MoEBlock(nn.Module):
        @nn.compact
        def __call__(self, x):
            return MoE(num_experts=4, hidden_size=32, intermediate_size=64,
                       top_k=2, mode="capacity_factor", capacity_factor=2.0,
                       dtype=jnp.float32, name="moe")(x)

    cfg = neuronx_distributed_config(tensor_parallel_size=2, expert_parallel_size=2)
    x = np.random.RandomState(0).randn(4, 8, 32).astype(np.float32)
    y = np.random.RandomState(1).randn(4, 8, 32).astype(np.float32)
    model = initialize_parallel_model(cfg, MoEBlock, jnp.zeros((4, 8, 32)))
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-2, weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(params, batch, rng):
        out, mut = model.module.apply({"params": params}, batch["x"], mutable=["losses"])
        return jnp.mean((out - batch["y"]) ** 2) + collect_aux_losses(mut)

    step = make_train_step(model, opt, loss_fn)
    losses = []
    for i in range(4):
        state, m = step(state, {"x": x, "y": y}, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# --- Mixtral model family + selective loading + EP checkpoints -------------

def _mixtral_cfg(**over):
    from neuronx_distributed_tpu.models.mixtral import MixtralConfig

    base = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, kv_size_multiplier=2, max_seq_len=64,
        dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
        num_experts=4, top_k=2,
    )
    base.update(over)
    return MixtralConfig(**base)


def test_mixtral_tp_ep_matches_dense():
    from flax.core import meta

    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM

    cfg = _mixtral_cfg(moe_mode="all_experts")
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 127)
    model = MixtralForCausalLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), ids)
    dense = meta.unbox(variables)
    golden = model.apply(dense, ids)

    st = ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                      expert_model_parallel_size=2)
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree

    sharded = jax.device_put(dense, named_sharding_tree(variables, st.mesh))
    with jax.set_mesh(st.mesh):
        out = jax.jit(model.apply)(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-4, atol=2e-4)


def test_mixtral_train_step_with_aux_loss():
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, mixtral_loss
    from neuronx_distributed_tpu.trainer import (
        create_train_state, initialize_parallel_model,
        initialize_parallel_optimizer, make_train_step,
        neuronx_distributed_config,
    )

    cfg = neuronx_distributed_config(
        tensor_parallel_size=2, expert_parallel_size=2,
        optimizer_config={"zero_one_enabled": True},
    )
    mcfg = _mixtral_cfg(moe_mode="capacity_factor", capacity_factor=2.0)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 127, (4, 16))
    labels = rs.randint(0, 127, (4, 16))
    model = initialize_parallel_model(cfg, lambda: MixtralForCausalLM(mcfg), ids)
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=3e-3, weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(params, batch, rng):
        return mixtral_loss(model.module, params, batch["ids"], batch["labels"])

    step = make_train_step(model, opt, loss_fn)
    losses = []
    for i in range(3):
        state, m = step(state, {"ids": ids, "labels": labels}, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_grouped_matches_all_experts_in_a_decode_step():
    """A serving decode step (seq=1) runs the grouped form; nothing is
    dropped, so it equals all_experts up to the order of additions."""
    from flax.core import meta

    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM

    tok = jax.random.randint(jax.random.PRNGKey(0), (2, 1), 0, 127)
    cfg_grp = _mixtral_cfg(decode=True)
    cfg_all = _mixtral_cfg(decode=True, moe_mode="all_experts")
    mg, ma = MixtralForCausalLM(cfg_grp), MixtralForCausalLM(cfg_all)
    variables = mg.init(jax.random.PRNGKey(0), tok)
    params = meta.unbox(variables)["params"]
    cache = meta.unbox(variables)["cache"]
    o_g, _ = mg.apply({"params": params, "cache": cache}, tok, mutable=["cache"])
    o_a, _ = ma.apply({"params": params, "cache": cache}, tok, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(o_g), np.asarray(o_a), rtol=1e-5, atol=1e-6)


def test_mixtral_generate():
    """KV-cached generation through the CausalLM serving stack (prefill and
    token-gen decode steps run the grouped expert path)."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM

    cfg = _mixtral_cfg()
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, 127)
    model = MixtralForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]
    lm = CausalLM(cfg, params, MixtralForCausalLM, buckets=(16,), max_batch=2)
    result = lm.generate(np.asarray(ids), max_new_tokens=4)
    assert result.tokens.shape == (1, 4)
    assert (result.lengths == 4).all()


# --- the grouped (serving) form against all_experts ------------------------

def _serving_moe(E, k, glu, norm, mode, dtype):
    from neuronx_distributed_tpu.moe import MoE

    return MoE(num_experts=E, hidden_size=32, intermediate_size=64, top_k=k,
               norm_topk_prob=norm, glu=glu, mode=mode, inference=True,
               dtype=dtype, param_dtype=jnp.float32)


@pytest.mark.parametrize("masked", [False, True], ids=["all_real", "masked"])
@pytest.mark.parametrize("shape", [(8, 1), (2, 512)], ids=["T8", "T2x512"])
@pytest.mark.parametrize("norm", [True, False], ids=["renorm", "as_is"])
@pytest.mark.parametrize("glu", [True, False], ids=["glu", "gelu"])
@pytest.mark.parametrize("E,k", [(8, 2), (64, 8), (4, 1)])
def test_grouped_equals_all_experts(E, k, glu, norm, shape, masked):
    """Serving's grouped matmul (the Pallas kernel, interpreted here) against
    the all_experts golden on the same parameters: bf16 operands, float32
    accumulation, the same products. The golden rounds gate and up to bf16
    before the activation and its output before the weighted sum, where the
    kernel keeps float32 up to its one store, so they agree within two bf16
    roundings (2 ** -6 of the largest output); a token that is not real
    comes out exactly zero, and finite. Two experts get a router column
    that no token can choose, so groups of no rows are always among them."""
    b, s = shape
    x = jax.random.normal(jax.random.PRNGKey(E + s), (b, s, 32), jnp.float32)
    live = (jax.random.uniform(jax.random.PRNGKey(7), (b, s)) < 0.6) if masked else None
    grouped = _serving_moe(E, k, glu, norm, "capacity_factor", jnp.bfloat16)
    golden = _serving_moe(E, k, glu, norm, "all_experts", jnp.bfloat16)
    params = golden.init(jax.random.PRNGKey(1), x)["params"]
    kernel = params["router"]["kernel"]
    params["router"]["kernel"] = kernel.at[:, 1].set(-10.0 * jnp.abs(kernel[:, 1])
                                                     ).at[:, E - 1].set(kernel[:, 0] - 1.0)
    want = golden.apply({"params": params}, x)
    got = jax.jit(lambda p, x, live: grouped.apply({"params": p}, x, live))(params, x, live)
    assert got.dtype == want.dtype and np.isfinite(np.asarray(got, np.float32)).all()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    real = np.ones((b, s), bool) if live is None else np.asarray(live)
    assert np.abs(got - want)[real].max() <= 2 ** -6 * np.abs(want).max()
    assert (got[~real] == 0).all()


@pytest.mark.parametrize("masked", [False, True], ids=["all_real", "masked"])
@pytest.mark.parametrize("E,k,T", [(8, 2, 8), (64, 8, 8), (4, 1, 1024), (8, 2, 1000),
                                   (8, 2, 4096), (64, 8, 512), (4, 1, 512)])
def test_group_sizes_sum_to_the_live_assignments(E, k, T, masked):
    """The sort that feeds the kernel: group sizes count the real tokens'
    choices and nothing else, the order lists each expert's rows together,
    and the visits' sub-tiles (the 512- and 1 024-row tiles of many rows are
    cut into them; one tile is one) multiply and store every row of a group
    exactly once."""
    from neuronx_distributed_tpu.kernels.grouped_matmul import (
        group_visits, row_tile, rows_multiplied, sub_tile)
    from neuronx_distributed_tpu.moe.expert_mlps import (
        grouped_rows_multiplied, sort_by_expert, token_class)

    rs = np.random.RandomState(T + E)
    chosen = np.stack([rs.choice(E, k, replace=False) for _ in range(T)])
    combine = np.zeros((T, E), np.float32)
    np.put_along_axis(combine, chosen, rs.uniform(0.1, 1.0, (T, k)), axis=1)
    live = rs.uniform(size=T) < 0.5 if masked else np.ones(T, bool)
    _, order, place, sizes = sort_by_expert(
        jnp.asarray(combine), k, jnp.asarray(live) if masked else None)
    order, place, sizes = np.asarray(order), np.asarray(place), np.asarray(sizes)
    assert sizes.sum() == live.sum() * k
    np.testing.assert_array_equal(sizes, (combine[live] > 0).sum(0))
    np.testing.assert_array_equal(order[place], np.arange(T * k))
    ends = np.cumsum(sizes)
    for e in range(E):                       # group e's rows chose expert e
        toks = order[ends[e] - sizes[e]: ends[e]] // k
        assert live[toks].all() and (combine[toks, e] > 0).all()
    tm, rows = row_tile(token_class(T) * k, E)       # as ``_grouped_experts`` tiles it
    sub = sub_tile(tm)
    visits = jax.tree.map(np.asarray, group_visits(jnp.asarray(sizes), rows, tm))
    covered, multiplied = np.zeros(rows, int), 0
    for v in range(int(visits.count)):
        g, t = visits.group[v], visits.tile[v]
        lo, hi = max(visits.offsets[g], t * tm), min(visits.offsets[g + 1], (t + 1) * tm)
        assert hi > lo                       # no visit without a row
        # the sub-tiles of the tile the kernel's loop runs for this visit
        # (``_kernel``'s bounds), and the rows of them it stores
        first, last = visits.offsets[g] - t * tm, visits.offsets[g + 1] - t * tm
        for s in range(max(first, 0) // sub, (min(last, tm) + sub - 1) // sub):
            at = t * tm + s * sub
            multiplied += sub
            covered[max(at, lo): min(at + sub, hi)] += 1
    assert (covered[: sizes.sum()] == 1).all() and (covered[sizes.sum():] == 0).all()
    assert (np.diff(visits.tile[: int(visits.count)]) >= 0).all()  # revisits are consecutive
    # ``moe_insert_rows_multiplied`` is this count: a sub-tile once for every
    # group with a row in it
    touched = sum((e - 1) // sub - (e - n) // sub + 1 for e, n in zip(ends, sizes) if n)
    assert int(rows_multiplied(jnp.asarray(sizes), tm)) == multiplied == touched * sub
    assert int(grouped_rows_multiplied(jnp.asarray(sizes)[None], T, k)) == multiplied
    assert sizes.sum() <= multiplied <= int(visits.count) * tm


def test_ep_sharded_checkpoint_roundtrip(tmp_path):
    """EP2xTP2-sharded Mixtral state saves and restores into the same
    shardings (reshard-on-load covers EP axes like any other; VERDICT r1
    asked for an EP-sharded checkpoint test)."""
    from flax.core import meta

    from neuronx_distributed_tpu.checkpoint import load_checkpoint, save_checkpoint
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM
    from neuronx_distributed_tpu.parallel.partitioning import named_sharding_tree

    cfg = _mixtral_cfg()
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 127)
    model = MixtralForCausalLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), ids)
    st = ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                      expert_model_parallel_size=2)
    shardings = named_sharding_tree(variables, st.mesh)
    params = jax.device_put(meta.unbox(variables), shardings)["params"]
    # expert weights really are ep-sharded
    gate = params["model"]["layers"]["block"]["moe"]["experts"]["gate"]
    assert "ep" in str(gate.sharding.spec)

    save_checkpoint(str(tmp_path / "ck"), "t0", params)
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), params
    )
    restored, _ = load_checkpoint(str(tmp_path / "ck"), "t0", target=target)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, restored,
    )
    r_gate = restored["model"]["layers"]["block"]["moe"]["experts"]["gate"]
    assert r_gate.sharding.spec == gate.sharding.spec


def test_mixtral_tied_embeddings():
    """Mixtral inherits the Llama head: tie_word_embeddings must reuse the
    embedding table (no separate lm_head params) — regression for the copy
    that dropped it (r2 review)."""
    from flax.core import meta

    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM

    cfg = _mixtral_cfg(tie_word_embeddings=True, moe_mode="all_experts")
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, 127)
    model = MixtralForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]
    assert "lm_head" not in params
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 8, cfg.vocab_size)
