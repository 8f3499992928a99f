"""End-to-end trainer tests on the 8-device CPU mesh.

Mirrors the reference's minimum slice (SURVEY §7.2): a 2-layer
ColumnParallel→RowParallel MLP trained with the full stack (config → sharded
init → ZeRO-1 AdamW → jitted step), checked for loss-trajectory parity
against a single-device dense run — the reference's golden-vs-control
methodology (test/integration/common/integration_test_utils.py:54-157).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.optimizer.zero1 import zero1_param_spec
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.parallel.layers import ColumnParallelLinear, RowParallelLinear
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)


class ParallelMLP(nn.Module):
    hidden: int = 32
    ffn: int = 64

    @nn.compact
    def __call__(self, x):
        x = ColumnParallelLinear(features=self.ffn, name="up")(x)
        x = nn.gelu(x)
        x = RowParallelLinear(features=self.hidden, name="down")(x)
        return x


def _loss_fn_builder(model):
    def loss_fn(params, batch, rng):
        out = model.apply(params, batch["x"])
        return jnp.mean((out - batch["y"]) ** 2)

    return loss_fn


def _train(tp, zero1, steps=5, use_master=True):
    cfg = neuronx_distributed_config(
        tensor_parallel_size=tp,
        optimizer_config={"zero_one_enabled": zero1, "grad_clipping": True, "max_grad_norm": 1.0},
        mixed_precision_config={"use_master_weights": use_master},
    )
    x = np.random.RandomState(0).randn(16, 8, 32).astype(np.float32)
    y = np.random.RandomState(1).randn(16, 8, 32).astype(np.float32)
    model = initialize_parallel_model(cfg, ParallelMLP, jnp.zeros((16, 8, 32)))
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-2, weight_decay=0.0)
    state = create_train_state(model, opt)
    step = make_train_step(model, opt, _loss_fn_builder(model))
    losses = []
    rng = jax.random.key(42)
    for _ in range(steps):
        state, metrics = step(state, {"x": x, "y": y}, rng)
        losses.append(float(metrics["loss"]))
    # one program for the whole run: a state leaf born with another sharding
    # than the step hands back (the counter once was) compiles the step twice
    assert step._cache_size() == 1
    ps.destroy_model_parallel()
    return losses


def test_tp_zero1_matches_dense_trajectory():
    ref = _train(tp=1, zero1=False)
    got = _train(tp=4, zero1=True)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
    assert got[-1] < got[0]  # actually learning


def test_plain_adamw_path():
    losses = _train(tp=2, zero1=False, use_master=False, steps=3)
    assert losses[-1] < losses[0]


def test_pallas_adamw_kernel_matches_jnp():
    """The single-pass Pallas AdamW kernel (optimizer/fused_kernel.py, run
    under the interpreter on CPU) must reproduce the jnp update exactly:
    same mu/nu/master math, params = cast of the new master."""
    from neuronx_distributed_tpu.optimizer.fused_kernel import (
        fused_adamw_leaf,
        leaf_supported,
    )

    n = 16384
    assert leaf_supported(n) and not leaf_supported(n - 128)
    rs = np.random.RandomState(5)
    g = jnp.asarray(rs.randn(n) * 2, jnp.bfloat16)
    mu = jnp.asarray(rs.randn(n) * 0.1, jnp.float32)
    nu = jnp.asarray(np.abs(rs.randn(n)) * 0.01, jnp.float32)
    ms = jnp.asarray(rs.randn(n), jnp.float32)
    b1, b2, eps, wd, lr, scl, bc1, bc2 = 0.9, 0.999, 1e-8, 0.01, 1e-2, 0.7, 0.5, 0.3
    scalars = jnp.asarray([[scl, lr, bc1, bc2]], jnp.float32)
    mu2, nu2, ms2, p2 = fused_adamw_leaf(
        g, mu, nu, ms, scalars, b1=b1, b2=b2, eps=eps, wd=wd,
        p_dtype=jnp.bfloat16)

    g32 = np.asarray(g, np.float32) * scl
    mu_ref = b1 * np.asarray(mu) + (1 - b1) * g32
    nu_ref = b2 * np.asarray(nu) + (1 - b2) * g32 * g32
    ms_ref = np.asarray(ms) - lr * (
        (mu_ref / bc1) / (np.sqrt(nu_ref / bc2) + eps) + wd * np.asarray(ms))
    np.testing.assert_allclose(np.asarray(mu2), mu_ref, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(nu2), nu_ref, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ms2), ms_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(
        jnp.asarray(ms2).astype(jnp.bfloat16)))


def test_kernel_step_matches_default_trajectory():
    """make_train_step(optimizer_kernel=True) — the shard_map + Pallas
    optimizer path (interpreted on CPU) — must track the default XLA-fused
    path's loss trajectory on a TP x ZeRO-1 model."""
    cfg = neuronx_distributed_config(
        tensor_parallel_size=2,
        optimizer_config={"zero_one_enabled": True, "grad_clipping": True,
                          "max_grad_norm": 1.0},
        mixed_precision_config={"use_master_weights": True},
    )
    x = np.random.RandomState(0).randn(16, 8, 32).astype(np.float32)
    y = np.random.RandomState(1).randn(16, 8, 32).astype(np.float32)

    def run(kernel):
        if ps.model_parallel_is_initialized():
            ps.destroy_model_parallel()
        model = initialize_parallel_model(cfg, ParallelMLP, jnp.zeros((16, 8, 32)))
        opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-2,
                                            weight_decay=0.0)
        state = create_train_state(model, opt)
        step = make_train_step(model, opt, _loss_fn_builder(model),
                               optimizer_kernel=kernel)
        losses = []
        for i in range(4):
            state, m = step(state, {"x": x, "y": y}, jax.random.key(i))
            losses.append(float(m["loss"]))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5, atol=1e-6)


def test_fused_update_and_params_matches_classic():
    """The fused single-pass optimizer (update_and_params: new params are
    the cast of the new master, clip scale folded into the grad cast) must
    track the classic updates/apply_updates path: identical master/moment
    states, params equal to the exact cast of the master."""
    from neuronx_distributed_tpu.optimizer.adamw import adamw_fp32_master
    from neuronx_distributed_tpu.parallel.grads import clip_grad_norm, get_grad_norm

    tx = adamw_fp32_master(1e-2, weight_decay=0.01)
    rs = np.random.RandomState(0)
    params = {"w": jnp.asarray(rs.randn(16, 8) * 3, jnp.bfloat16),
              "b": jnp.asarray(rs.randn(8), jnp.float32)}
    grads = {"w": jnp.asarray(rs.randn(16, 8) * 5, jnp.bfloat16),
             "b": jnp.asarray(rs.randn(8) * 5, jnp.float32)}
    max_norm = 1.0

    # classic: materialized clipped grads -> updates -> apply
    s0 = tx.init(params)
    clipped, norm = clip_grad_norm(grads, max_norm)
    upd, s_classic = tx.update(clipped, s0, params)
    p_classic = optax.apply_updates(params, upd)

    # fused: scale folded in, params emitted directly
    scale = jnp.clip(max_norm / (get_grad_norm(grads) + 1e-6), max=1.0)
    p_fused, s_fused = tx.update_and_params(grads, tx.init(params), params,
                                            scale=scale)

    # moments/master agree (fused applies the clip scale in fp32 — strictly
    # tighter than the classic bf16 round-trip of the scaled grads)
    for k in ("mu", "nu", "master"):
        got = jax.tree.map(np.asarray, getattr(s_fused, k))
        want = jax.tree.map(np.asarray, getattr(s_classic, k))
        np.testing.assert_allclose(got["w"], want["w"], rtol=1e-2, atol=1e-6)
        np.testing.assert_allclose(got["b"], want["b"], rtol=1e-5, atol=1e-8)
    # fused params are the EXACT cast of the fused master
    np.testing.assert_array_equal(
        np.asarray(p_fused["w"]),
        np.asarray(s_fused.master["w"].astype(jnp.bfloat16)))
    np.testing.assert_array_equal(
        np.asarray(p_fused["b"]), np.asarray(s_fused.master["b"]))
    # and numerically track the classic path's params
    np.testing.assert_allclose(
        np.asarray(p_fused["w"], np.float32),
        np.asarray(p_classic["w"], np.float32), rtol=2e-2, atol=1e-3)

    # without clipping the two paths are algebraically identical in fp32
    upd2, s2 = tx.update(grads, tx.init(params), params)
    p2f, s2f = tx.update_and_params(grads, tx.init(params), params)
    for k in ("mu", "nu", "master"):
        got = jax.tree.map(np.asarray, getattr(s2f, k))
        want = jax.tree.map(np.asarray, getattr(s2, k))
        np.testing.assert_array_equal(got["w"], want["w"])
        np.testing.assert_array_equal(got["b"], want["b"])


def test_zero1_param_spec_assignment():
    ps.initialize_model_parallel(tensor_model_parallel_size=2)  # dp=4 → edp=4
    # unsharded 2D param: first divisible dim gets the DP axes
    assert zero1_param_spec(P(None, None), (64, 32)) == P("edp", None)
    # TP-sharded dim extended when divisible, else other dim used
    assert zero1_param_spec(P(None, "tp"), (64, 32)) == P("edp", "tp")
    # nothing divides → replicated state
    assert zero1_param_spec(P(None), (3,)) == P()


def test_zero1_state_is_dp_sharded():
    cfg = neuronx_distributed_config(tensor_parallel_size=2)
    model = initialize_parallel_model(cfg, ParallelMLP, jnp.zeros((4, 8, 32)))
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-3)
    state = create_train_state(model, opt)
    # find the mu tree: every param-shaped leaf must have >1 shard groups
    mu = state.opt_state.mu
    leaf = jax.tree_util.tree_leaves(mu)[0]
    # sharded over edp(4) somewhere → number of distinct shards > tp alone
    # stringify: shard .index is a tuple of slices, unhashable before py3.12
    ndevs_with_data = len({str(s.index) for s in leaf.addressable_shards})
    assert ndevs_with_data > 2, f"opt state not ZeRO-sharded: {leaf.sharding}"
    ps.destroy_model_parallel()


def test_grad_accum_matches_full_batch():
    """grad_accum_steps=2 inside the jitted step (lax.scan accumulation)
    must reproduce the full-batch step exactly when microbatch losses are
    equal-weight (mean-of-means == global mean; the same contract the
    reference's loss/grad_accum_steps division assumes,
    module_llama.py:105)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )

    cfg = neuronx_distributed_config(tensor_parallel_size=2)
    # fp32 compute: in bf16 the per-microbatch rounding alone perturbs grads
    # ~3e-4, which adam's m/sqrt(v) normalization amplifies to lr-scale param
    # diffs — the identity under test is the fp32 algebraic one
    lcfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                       num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=16,
                       use_flash_attention=False, remat_policy=None,
                       dtype=jnp.float32)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 128, (8, 16)))
    labels = jnp.asarray(rs.randint(0, 128, (8, 16)))  # all valid: exact split
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-2,
                                        weight_decay=0.0)

    def loss_fn(params, b, rng):
        return model.module.apply({"params": params}, b["ids"], b["labels"],
                                  method=LlamaForCausalLM.loss)

    batch = {"ids": ids, "labels": labels}
    s_full = create_train_state(model, opt)
    s_acc = jax.tree.map(lambda x: x, s_full)  # same init
    # donate=False: both steps consume the SAME initial state buffers
    step_full = make_train_step(model, opt, loss_fn, donate=False)
    step_acc = make_train_step(model, opt, loss_fn, grad_accum_steps=2,
                               donate=False)
    s_full, m_full = step_full(s_full, batch, jax.random.key(0))
    s_acc, m_acc = step_acc(s_acc, batch, jax.random.key(0))
    np.testing.assert_allclose(float(m_acc["loss"]), float(m_full["loss"]),
                               rtol=1e-6)
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b_: float(jnp.max(jnp.abs(a - b_))), s_acc.params, s_full.params)))
    assert worst < 1e-5, f"params diverged after one update: {worst}"


# ---------------------------------------------- the weights' program (ISSUE 57)
# What varies from run to run reaches the weights' program as ARGUMENTS: the
# keys and the array leaves of the example arguments. One lowered text for
# every seed is one entry of the persistent compile cache for every seed.

_TINY_LLAMA = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
                   num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
                   dtype=jnp.float32, use_flash_attention=False, remat_policy=None)


def _tiny_llama():
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(**_TINY_LLAMA))


class FollowsItsExample(nn.Module):
    """Parameter shapes that follow the example's width and two example
    keywords that are no arrays: ``wide`` and ``times`` decide the trace
    (a tracer in their place raises), ``bias`` is ``None`` or an array."""

    @nn.compact
    def __call__(self, x, *, wide=False, times=1, bias=None):
        h = ColumnParallelLinear(features=(32 if wide else 8) * times, name="up")(x)
        h = nn.gelu(h if bias is None else h + bias)
        return RowParallelLinear(features=x.shape[-1], name="down")(h)


@pytest.fixture
def weights_texts(monkeypatch):
    """The lowered text of every weights' program built in the test."""
    from neuronx_distributed_tpu.utils.compile_cache import compile_log

    texts, staged = [], compile_log.staged

    def recording(jitted, *args):
        texts.append(jitted.lower(*args).as_text())
        return staged(jitted, *args)

    monkeypatch.setattr(compile_log, "staged", recording)
    return texts


def _ids(fill, shape=(1, 8)):
    return np.full(shape, fill, np.int32)


@pytest.mark.parametrize("what, one, other, same", [
    ("two seeds", dict(seed=1), dict(seed=2), True),
    ("two seeds of the chip's generator", dict(seed=1, impl="rbg"),
     dict(seed=2147489203, impl="rbg"), True),
    ("two contents of the example ids", dict(ids=_ids(0)), dict(ids=_ids(7)), True),
    ("a numpy and a jax example", dict(ids=_ids(3)), dict(ids=jnp.asarray(_ids(5))), True),
    ("two kinds of key", dict(impl="threefry2x32"), dict(impl="rbg"), False),
    ("two example widths that the weights follow",
     dict(module=FollowsItsExample, ids=np.zeros((2, 4, 8), np.float32)),
     dict(module=FollowsItsExample, ids=np.zeros((2, 4, 16), np.float32)), False),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else "")
def test_the_weights_program_is_one_text_for_what_varies_from_run_to_run(
        weights_texts, what, one, other, same):
    def build(seed=0, impl="threefry2x32", ids=_ids(0), module=_tiny_llama):
        nxd = neuronx_distributed_config(tensor_parallel_size=1)
        initialize_parallel_model(nxd, module, ids,
                                  rngs={"params": jax.random.key(seed, impl=impl)})
        ps.destroy_model_parallel()

    build(**one)
    build(**other)
    first, second = weights_texts
    assert (first == second) == same, what
    # the key is the program's one argument: an initialiser reads shapes, never
    # values, so jit prunes the example (and the forward is dead code)
    for text in weights_texts:
        taken = text[text.index("@main("):].split(") ->")[0]
        assert "%arg0" in taken and "%arg1" not in taken


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
@pytest.mark.parametrize("tp, case", [(1, "llama"), (2, "llama"), (1, "keywords"), (4, "keywords")])
def test_the_weights_drawn_are_the_closed_over_forms_bit_for_bit(impl, tp, case):
    """The oracle is the form the parent compiled: keys and example arguments
    closed over, ``jax.jit(lambda: module.init(rngs, ids))``, a program a seed."""
    from flax.core import meta

    rngs = {"params": jax.random.key(2147489203, impl=impl)}
    if case == "llama":
        module, args, kwargs = _tiny_llama(), (_ids(5, (2, 8)),), {}
    else:
        module, args = FollowsItsExample(), (np.ones((2, 4, 8), np.float32),)
        kwargs = dict(wide=True, times=2, bias=jnp.full((64,), 0.5))
    nxd = neuronx_distributed_config(tensor_parallel_size=tp)
    model = initialize_parallel_model(nxd, lambda: module, *args, rngs=rngs, **kwargs)
    oracle = jax.jit(lambda: meta.unbox(module.init(rngs, *args, **kwargs))["params"],
                     out_shardings=model.param_shardings())()
    got, want = jax.tree.leaves(model.params), jax.tree.leaves(oracle)
    assert len(got) == len(want) > 2
    for a, b in zip(got, want):
        assert a.sharding == b.sharding and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if tp > 1:                                # born on its shard, as before
        assert any("tp" in str(leaf.sharding.spec) for leaf in got)
    if case == "keywords":
        assert model.params["up"]["kernel"].shape == (8, 64)
    # another seed draws other weights through the same form
    again = initialize_parallel_model(
        nxd, lambda: module, *args, rngs={"params": jax.random.key(1, impl=impl)}, **kwargs)
    assert not all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(got, jax.tree.leaves(again.params)))
