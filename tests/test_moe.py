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


# --- a held share of a wider router's picks: the compact passes -------------

def _combine_of(picks, routed, rs):
    """``combine (T, routed)`` float32: a weight in 0.1-1.0 at each of a
    token's ``picks (T, k)``, zero elsewhere."""
    combine = np.zeros((len(picks), routed), np.float32)
    np.put_along_axis(combine, picks, rs.uniform(0.1, 1.0, picks.shape).astype(np.float32),
                      axis=1)
    return combine


def _share_case(case):
    """``(T, k, held, routed, combine (T, held), live (T,) or None)`` of a
    grouped call that holds ``held`` of ``routed`` experts: each token picks
    ``k`` of the routed at random and the held are the first."""
    T, k, held, routed = 512, 4, 4, 32
    rs = np.random.RandomState(11)
    live = None
    if case == "all_here":                  # every pick on a held expert
        picks = np.stack([rs.permutation(held)[:k] for _ in range(T)])
    elif case == "none_here":               # every pick elsewhere
        picks = np.stack([held + rs.permutation(routed - held)[:k] for _ in range(T)])
    else:
        if case == "masked_and_padded":     # 300 tokens in a class of 512
            T, live = 300, rs.uniform(size=300) < 0.7
        elif case == "sliced":              # three slices of GROUPED_TOKENS (patched to 512)
            T = 1300
        picks = np.stack([rs.permutation(routed)[:k] for _ in range(T)])
    return T, k, held, routed, _combine_of(picks, routed, rs)[:, :held], live


@pytest.mark.parametrize("case", ["an_eighth_here", "all_here", "none_here",
                                  "masked_and_padded", "sliced"])
def test_a_held_share_goes_by_compact_passes(case, monkeypatch):
    """A layer that holds a share hands on ``row_bound`` rows of the sorted
    list a pass (here 512 of a class's 2 048 picks): against all_experts on
    the same weights at the grouped tests' tolerance, whatever the routing.
    The usual eighth is one pass; every pick on a held expert is ``M / R``
    passes and nothing is dropped; no pick here is no pass and zeros; tokens
    that are not live and a class's padding choose nothing; a call longer
    than ``GROUPED_TOKENS`` goes by slices, each with its own passes. The
    passes the serving counter reckons (``share_call_sums``) are
    ``ceil(n / R)`` a slice and the rows handled ``passes x R``."""
    from neuronx_distributed_tpu.kernels.grouped_matmul import row_tile
    from neuronx_distributed_tpu.moe import expert_mlps
    from neuronx_distributed_tpu.moe.expert_mlps import ExpertMLPs

    monkeypatch.setattr(expert_mlps, "GROUPED_TOKENS", 512)
    T, k, held, routed, combine, live = _share_case(case)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 32), jnp.float32).astype(jnp.bfloat16)
    make = lambda mode: ExpertMLPs(num_experts=held, hidden_size=32, intermediate_size=64,  # noqa: E731
                                   mode=mode, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    combine = jnp.asarray(combine, jnp.bfloat16)
    params = make("grouped").init(jax.random.PRNGKey(1), x, combine, top_k=k)
    calls = []
    real_call = expert_mlps._grouped_experts
    monkeypatch.setattr(expert_mlps, "_grouped_experts",
                        lambda x, *a, **kw: calls.append(x.shape[0]) or real_call(x, *a, **kw))
    got = make("grouped").apply(params, x, combine, top_k=k, routed=routed,
                                live=None if live is None else jnp.asarray(live))
    want = make("all_experts").apply(params, x, combine)
    assert got.dtype == want.dtype and np.isfinite(np.asarray(got, np.float32)).all()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    alive = np.ones(T, bool) if live is None else live
    assert np.abs(got - want)[alive].max() <= 2 ** -6 * max(np.abs(want).max(), 1e-6)
    assert (got[~alive] == 0).all()
    if case == "none_here":
        assert (got == 0).all()
    else:
        assert np.abs(got).max() > 0

    assert calls == ([512, 512, 512] if case == "sliced" else [512])
    bound = expert_mlps.row_bound(512, k, held, routed)
    assert bound == 512 and row_tile(bound, held)[0] == 512     # of 2 048, 512-row tiles
    chosen = (np.asarray(combine, np.float32) > 0) & alive[:, None]
    passes = [-(-int(chosen[at: at + 512].sum()) // bound) for at in range(0, T, 512)]
    assert passes == {"an_eighth_here": [1], "all_here": [4], "none_here": [0],
                      "masked_and_padded": [1], "sliced": [1, 1, 1]}[case]
    handled, multiplied, counted = np.asarray(
        expert_mlps.share_call_sums(jnp.asarray(chosen)[None], k, routed))
    assert counted == sum(passes) and handled == sum(passes) * bound
    assert chosen.sum() <= multiplied <= handled + 64 * held * sum(passes)


# sha1 of ``_grouped_experts``' lowered text (computed by the same lines at
# PR 61, whose choice-major combine is the one change to ``_grouped_whole``
# since PR 51's parent): a layer that holds every expert, and a share's call
# of one tile, are ONE program each, whatever is done to ``_grouped_share``.
PARENTS_GROUPED = {
    (512, 2, 8, None): "b2f6b63fa241f0c1fb49d8832c5a10512d033f14",
    (1024, 8, 64, None): "0bcae1d22426f83500ab9dda853bf5e198b6adb8",
    (8, 4, 4, 32): "79152dcc941782c99c8b9618e92712cae1454212",
    (16, 4, 4, 32): "c70888689409e6a4ab96ac5b47c532f9515054c1",
}


@pytest.mark.parametrize("case", list(PARENTS_GROUPED),
                         ids=["all_held_T512", "all_held_T1024", "share_T8", "share_T16"])
def test_all_held_and_one_tile_calls_lower_to_the_parents_text(case):
    import hashlib

    from neuronx_distributed_tpu.moe.expert_mlps import _grouped_experts, row_bound

    T, k, E, routed = case
    assert row_bound(T, k, E, routed) == T * k
    sds = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    text = _grouped_experts.lower(
        sds((T, 32), bf16), sds((T, E), bf16), sds((T,), bool), sds((), jnp.int32),
        sds((2, E, 32, 64), bf16), sds((2, E, 32, 64), bf16), sds((2, E, 64, 32), bf16),
        top_k=k, glu=True, dtype=jnp.dtype(bf16), interpret=True, routed=routed).as_text()
    assert hashlib.sha1(text.encode()).hexdigest() == PARENTS_GROUPED[case]


@pytest.mark.parametrize("tokens,top_k,held,routed,bound", [
    (4096, 10, 32, 256, 10240),      # Laguna's 1 x 4096 insert: a quarter
    (2048, 6, 20, 160, 3072),        # DeepSeek-V2's 1 x 2048
    (16384, 10, 32, 256, 40960),     # a slice of GROUPED_TOKENS
    (8, 10, 32, 256, 80),            # a decode step: one tile, the whole list
    (64, 10, 32, 256, 320),          # 640 picks: a quarter would be under a tile, a half holds
    (128, 10, 32, 256, 320),
    (4096, 2, 8, None, 8192),        # every expert held
    (4096, 2, 8, 8, 8192),
    (4096, 4, 8, 16, 16384),         # half held: twice the expectation is the list
    (4096, 4, 8, 32, 8192),          # a quarter held: half the list
])
def test_row_bound_is_twice_the_share_and_a_tile_at_least(tokens, top_k, held, routed, bound):
    from neuronx_distributed_tpu.moe.expert_mlps import row_bound

    got = row_bound(tokens, top_k, held, routed)
    assert got == bound and (tokens * top_k) % got == 0
    if routed and got < tokens * top_k:
        assert got >= 256 and got * routed >= 2 * tokens * top_k * held


# --- the combine of a list taken whole: choice-major, one float32 fusion ----

def _picks(T, k, E, routed, seed):
    """``combine (T, E)`` float32 of tokens that pick ``k`` of ``routed``
    experts at random, the first ``E`` of them held here."""
    rs = np.random.RandomState(seed)
    picks = np.stack([rs.permutation(routed)[:k] for _ in range(T)])
    return _combine_of(picks, routed, rs)[:, :E]


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "half_held"])
@pytest.mark.parametrize("E,k", [(8, 2), (64, 4), (64, 8)], ids=["mixtral", "xing", "olmoe"])
def test_the_whole_lists_combine_is_the_token_major_sum(E, k, share, monkeypatch):
    """``_grouped_whole`` brings the down kernel's rows back in CHOICE-major
    order and sums the leading axis (PR 61: no ``(T, top_k, H)`` array, whose
    few choices sit on the axis the TPU tiles). Against the parent's
    arithmetic written out here, the token-major gather contracted
    ``tkh,tk->th`` in float32, on ONE buffer that stands in for the kernel's
    output: equal to float32 rounding (the reduction's order is the
    compiler's). A third of the tokens are dead and, with half the router's
    experts held, a token's picks that fell elsewhere have weight zero: the
    rows of no group hold NaN, and a row that was scaled and not selected
    away would carry it into the sum."""
    from neuronx_distributed_tpu.kernels.grouped_matmul import row_tile
    from neuronx_distributed_tpu.moe import expert_mlps

    T, H = 256, 128
    combine = jnp.asarray(_picks(T, k, E, 2 * E if share else E, seed=E + k))
    live = np.random.RandomState(5).uniform(size=T) < 0.67
    weight, order, place, sizes = expert_mlps.sort_by_expert(combine, k, jnp.asarray(live), share)
    n = int(sizes.sum())
    assert n == int(((np.asarray(combine) > 0) & live[:, None]).sum()) and 0 < n < T * k
    assert expert_mlps.row_bound(T, k, E, 2 * E if share else None) == T * k
    _, rows = row_tile(T * k, E)
    buffer = jax.random.normal(jax.random.PRNGKey(2), (rows, H), jnp.float32)
    buffer = buffer.at[n:].set(jnp.nan).astype(jnp.bfloat16)        # the rows of no group
    monkeypatch.setattr(expert_mlps, "grouped_matmul", lambda *a, **kw: buffer)
    x = jnp.zeros((T, H), jnp.bfloat16)
    got = expert_mlps._grouped_whole(
        x, weight, order, place, sizes, jnp.asarray(live), jnp.int32(0), None, None, None,
        T * k, True, jnp.dtype(jnp.bfloat16), True, share)

    rows_back = np.asarray(buffer.astype(jnp.float32))[np.asarray(place)].reshape(T, k, H)
    real = live[:, None] & ((np.asarray(weight) > 0) if share else np.ones((T, k), bool))
    assert np.isnan(rows_back[~real]).all() and np.isfinite(rows_back[real]).all()
    want = np.einsum("tkh,tk->th", np.where(real[:, :, None], rows_back, 0),
                     np.asarray(weight, np.float32))
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == (T, H) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * k * 2.0 ** -24 * np.abs(want).max())
    assert (got[~live] == 0).all() and np.abs(got[live]).max() > 0


def test_a_list_taken_whole_and_by_passes_agree():
    """A share's list of four passes (``_grouped_share``, 512 rows a pass)
    and the same list taken whole (``_grouped_whole``): the same rows out of
    the same kernels, summed slot by slot there and over the leading axis
    here, so equal to float32 rounding and not bit for bit."""
    from neuronx_distributed_tpu.moe import expert_mlps

    T, k, E, routed, H, I = 512, 4, 4, 32, 32, 64
    combine = jnp.asarray(_picks(T, k, E, routed, seed=11))
    live = jnp.asarray(np.random.RandomState(6).uniform(size=T) < 0.8)
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(keys[0], (T, H), jnp.float32).astype(jnp.bfloat16)
    gate, up = (jax.random.normal(key, (1, E, H, I), jnp.float32).astype(jnp.bfloat16) * 0.2
                for key in keys[1:3])
    down = jax.random.normal(keys[3], (1, E, I, H), jnp.float32).astype(jnp.bfloat16) * 0.2
    bound = expert_mlps.row_bound(T, k, E, routed)
    assert bound == 512 < T * k
    sorted_list = expert_mlps.sort_by_expert(combine, k, live, True)
    whole, by_passes = (
        np.asarray(form(x, *sorted_list, live, jnp.int32(0), gate, up, down, rows, True,
                        jnp.dtype(jnp.bfloat16), True, True))
        for form, rows in ((expert_mlps._grouped_whole, T * k),
                           (expert_mlps._grouped_share, bound)))
    assert whole.dtype == by_passes.dtype == np.float32 and np.abs(whole).max() > 0
    np.testing.assert_allclose(whole, by_passes, rtol=0,
                               atol=4 * k * 2.0 ** -24 * np.abs(whole).max())
    assert (whole[~np.asarray(live)] == 0).all()


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
