"""Pipeline tests: pure-logic schedule invariants (reference
test_scheduler.py methodology, SURVEY §4.1) + SPMD engine correctness on the
8-device CPU mesh (PP alone and PP x TP x DP), golden vs the non-PP model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.pipeline import schedules as S
from neuronx_distributed_tpu.parallel import mesh as ps


# --- schedule generators (no devices) --------------------------------------

@pytest.mark.parametrize("pp,mb,chunks", [(2, 4, 2), (2, 8, 4), (4, 8, 2),
                                          (4, 16, 4), (8, 16, 2)])
def test_interleaved_1f1b_global_invariants(pp, mb, chunks):
    """The tick-aligned interleaved-1F1B table that drives the SPMD engine:
    every unit scheduled once, ring-latency-1 dependencies hold, one fwd and
    one bwd unit per (tick, rank), stash capacity flat in microbatch count,
    and the bubble beats the plain 1F1B equivalent in chunk-ticks."""
    from collections import Counter

    g = S.interleaved_1f1b_global(pp, mb, chunks)
    V = pp * chunks
    assert len(g.exec_f) == len(g.exec_b) == pp * chunks * mb
    for (m, v), t in g.exec_f.items():
        if v > 0:
            assert g.exec_f[(m, v - 1)] < t  # ring hop is >= 1 tick
    for (m, v), t in g.exec_b.items():
        if v < V - 1:
            assert g.exec_b[(m, v + 1)] < t
        else:
            assert g.exec_f[(m, v)] <= t     # loss vjp may be same tick
    cf = Counter((t, v % pp) for (m, v), t in g.exec_f.items())
    cb = Counter((t, v % pp) for (m, v), t in g.exec_b.items())
    assert max(cf.values()) == 1 and max(cb.values()) == 1
    # 1F1B memory property: stash is flat in mb
    g2 = S.interleaved_1f1b_global(pp, 4 * mb, chunks)
    assert g2.x_slots == g.x_slots and g2.dy_slots == g.dy_slots
    # VPP bubble property: no more chunk-ticks than plain 1F1B's
    # (mb + 2(pp-1)) full-stage ticks x chunks chunk-units each; strictly
    # fewer once the pipeline is deep enough for the bubble to matter
    plain = (mb + 2 * (pp - 1)) * chunks
    assert g.ticks <= plain
    if pp >= 4:
        assert g.ticks < plain

@pytest.mark.parametrize("pp", [2, 4, 8])
@pytest.mark.parametrize("mb", [1, 4, 8, 32])
def test_1f1b_counts_and_order(pp, mb):
    for rank in range(pp):
        steps = list(S.train_1f1b_schedule(rank, pp, mb))
        tasks = [t for step in steps for t in step]
        fwd = [t for t in tasks if isinstance(t, S.ForwardStep)]
        bwd = [t for t in tasks if isinstance(t, S.BackwardStep)]
        assert len(fwd) == mb and len(bwd) == mb
        # microbatches in order
        assert [t.microbatch for t in fwd] == list(range(mb))
        assert [t.microbatch for t in bwd] == list(range(mb))
        # a backward never precedes its forward
        seen_f = set()
        for t in tasks:
            if isinstance(t, S.ForwardStep):
                seen_f.add(t.microbatch)
            if isinstance(t, S.BackwardStep):
                assert t.microbatch in seen_f
        # in-flight bound: warmup depth decreases with rank (1F1B memory bound)
        in_flight = 0
        peak = 0
        for t in tasks:
            if isinstance(t, S.ForwardStep):
                in_flight += 1
                peak = max(peak, in_flight)
            if isinstance(t, S.BackwardStep):
                in_flight -= 1
        assert peak <= min(pp - rank, mb)
        assert isinstance(tasks[-1], S.ReduceGrads)


@pytest.mark.parametrize("pp,mb", [(2, 4), (4, 8)])
def test_1f1b_send_recv_pairing(pp, mb):
    """Rank r's SendForward sequence == rank r+1's RecvForward sequence, and
    r+1's SendBackward == r's RecvBackward (deadlock-freedom invariant the
    reference enforces by graph-loading order, comm.py:27-35)."""
    for r in range(pp - 1):
        a = [t for st in S.train_1f1b_schedule(r, pp, mb) for t in st]
        b = [t for st in S.train_1f1b_schedule(r + 1, pp, mb) for t in st]
        send_f = [t.microbatch for t in a if isinstance(t, S.SendForward)]
        recv_f = [t.microbatch for t in b if isinstance(t, S.RecvForward)]
        assert send_f == recv_f
        send_b = [t.microbatch for t in b if isinstance(t, S.SendBackward)]
        recv_b = [t.microbatch for t in a if isinstance(t, S.RecvBackward)]
        assert send_b == recv_b


def test_inference_schedule():
    steps = list(S.inference_schedule(1, 4, 3))
    tasks = [t for st in steps for t in st]
    assert [t.microbatch for t in tasks if isinstance(t, S.ForwardStep)] == [0, 1, 2]
    assert all(not isinstance(t, S.BackwardStep) for t in tasks)


@pytest.mark.parametrize("pp,mb,chunks", [(2, 4, 2), (4, 8, 2)])
def test_interleaved_counts(pp, mb, chunks):
    for rank in range(pp):
        tasks = [t for st in S.interleaved_schedule(rank, pp, mb, chunks) for t in st]
        fwd = [t for t in tasks if isinstance(t, S.ForwardStep)]
        bwd = [t for t in tasks if isinstance(t, S.BackwardStep)]
        assert len(fwd) == mb * chunks
        assert len(bwd) == mb * chunks
        assert {(t.chunk, t.microbatch) for t in fwd} == {
            (c, m) for c in range(chunks) for m in range(mb)
        }


# --- SPMD engine -----------------------------------------------------------

def _tiny_cfg(**over):
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    base = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=4, num_kv_heads=4, max_seq_len=32, dtype=jnp.float32,
        use_flash_attention=False, remat_policy=None,
    )
    base.update(over)
    return LlamaConfig(**base)


def test_pp_matches_dense_forward():
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama

    cfg = _tiny_cfg()
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 127)

    # golden: same params through the non-PP stage math (plain scan, no mesh)
    pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=2, remat=False)
    params = pm.init(jax.random.PRNGKey(2), ids)

    def dense_apply(params, ids):
        # identical math without the pipeline: embed -> scan all layers -> norm -> head
        from neuronx_distributed_tpu.models.llama import rotary_embedding
        x = pm._embed.apply({"params": params["embed"]}, ids)
        cos, sin = rotary_embedding(jnp.arange(ids.shape[1]), cfg.head_dim_, cfg.rope_theta,
                                    dtype=x.dtype)
        x = pm._stage_fn(params["layers"]["block"], x, cos, sin)
        x = pm._norm.apply({"params": params["final_norm"]}, x)
        return pm._head.apply({"params": params["lm_head"]}, x)

    golden = dense_apply(params, ids)

    st = ps.initialize_model_parallel(pipeline_model_parallel_size=4)
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings
    specs = pm.param_specs(ids)
    sharded = jax.device_put(params, specs_to_shardings(specs, st.mesh))
    with jax.set_mesh(st.mesh):
        out = jax.jit(pm.apply)(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-4, atol=2e-4)

    # loss path too
    with jax.set_mesh(st.mesh):
        loss = jax.jit(pm.loss)(sharded, ids, labels)
    assert np.isfinite(float(loss))


def test_pp_tp_dp_train_step():
    """PP2 x TP2 x DP2 full train step via the trainer: loss decreases."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.trainer import (
        create_train_state, initialize_parallel_optimizer, make_train_step,
        neuronx_distributed_config,
    )

    nxd_cfg = neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2,
        optimizer_config={"zero_one_enabled": True},
    )
    ps.initialize_model_parallel(tensor_model_parallel_size=2, pipeline_model_parallel_size=2)
    cfg = _tiny_cfg()
    ids = np.random.RandomState(0).randint(0, 127, (8, 16))
    labels = np.random.RandomState(1).randint(0, 127, (8, 16))
    pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=2)
    model = pm.as_parallel_model(jnp.asarray(ids))
    opt = initialize_parallel_optimizer(nxd_cfg, model, learning_rate=3e-3, weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(params, batch, rng):
        return pm.loss(params, batch["ids"], batch["labels"])

    step = make_train_step(model, opt, loss_fn)
    losses = []
    for i in range(3):
        state, m = step(state, {"ids": ids, "labels": labels}, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_pp_loss_matches_dense_loss_exactly():
    """v2 per-microbatch scalar loss == dense full-batch CE (exact token
    weighting, including ignore_index), with NO logits materialization."""
    from neuronx_distributed_tpu.models.llama import rotary_embedding
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean

    cfg = _tiny_cfg()
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 127)
    labels = np.array(jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 127))
    labels[:, :3] = -100  # exercise ignore_index weighting across microbatches
    labels = jnp.asarray(labels)

    pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=2, remat=False)
    params = pm.init(jax.random.PRNGKey(2), ids)

    x = pm._embed.apply({"params": params["embed"]}, ids)
    cos, sin = rotary_embedding(jnp.arange(ids.shape[1]), cfg.head_dim_,
                                cfg.rope_theta, dtype=x.dtype)
    h = pm._stage_fn(params["layers"]["block"], x, cos, sin)
    h = pm._norm.apply({"params": params["final_norm"]}, h)
    golden = parallel_cross_entropy_mean(
        pm._head.apply({"params": params["lm_head"]}, h), labels, ignore_index=-100
    )

    st = ps.initialize_model_parallel(pipeline_model_parallel_size=4)
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    sharded = jax.device_put(params, specs_to_shardings(pm.param_specs(ids), st.mesh))
    with jax.set_mesh(st.mesh):
        loss = jax.jit(pm.loss)(sharded, ids, labels)
    np.testing.assert_allclose(float(loss), float(golden), rtol=1e-5)


def test_vpp_interleaved_matches_dense():
    """VPP (num_chunks=2) executes the interleaved schedule: forward and loss
    must match the canonical-order dense golden bit-for-bit (same init)."""
    from neuronx_distributed_tpu.models.llama import rotary_embedding
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean

    cfg = _tiny_cfg()
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 127)
    pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=2, remat=False,
                        num_chunks=2)
    st = ps.initialize_model_parallel(pipeline_model_parallel_size=2)
    params = pm.init(jax.random.PRNGKey(2), ids)

    canon = {**params, "layers": {"block": pm.canonical_layer_params(params)}}
    x = pm._embed.apply({"params": canon["embed"]}, ids)
    cos, sin = rotary_embedding(jnp.arange(ids.shape[1]), cfg.head_dim_,
                                cfg.rope_theta, dtype=x.dtype)
    h = pm._stage_fn(canon["layers"]["block"], x, cos, sin)
    h = pm._norm.apply({"params": canon["final_norm"]}, h)
    logits_golden = pm._head.apply({"params": canon["lm_head"]}, h)
    loss_golden = parallel_cross_entropy_mean(logits_golden, labels, ignore_index=-100)

    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    sharded = jax.device_put(params, specs_to_shardings(pm.param_specs(ids), st.mesh))
    with jax.set_mesh(st.mesh):
        out = jax.jit(pm.apply)(sharded, ids)
        loss = jax.jit(pm.loss)(sharded, ids, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(logits_golden),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(loss), float(loss_golden), rtol=1e-5)


def test_vpp_train_step():
    """PP2 x chunks2 end-to-end through the trainer."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.trainer import (
        create_train_state, initialize_parallel_optimizer, make_train_step,
        neuronx_distributed_config,
    )

    nxd_cfg = neuronx_distributed_config(
        pipeline_parallel_size=2, optimizer_config={"zero_one_enabled": True},
    )
    ps.initialize_model_parallel(pipeline_model_parallel_size=2)
    cfg = _tiny_cfg()
    ids = np.random.RandomState(0).randint(0, 127, (4, 16))
    labels = np.random.RandomState(1).randint(0, 127, (4, 16))
    pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=2, num_chunks=2)
    model = pm.as_parallel_model(jnp.asarray(ids))
    opt = initialize_parallel_optimizer(nxd_cfg, model, learning_rate=3e-3,
                                        weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(params, batch, rng):
        return pm.loss(params, batch["ids"], batch["labels"])

    step = make_train_step(model, opt, loss_fn)
    losses = []
    for i in range(3):
        state, m = step(state, {"ids": ids, "labels": labels}, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_loss_path_memory_below_logits_path():
    """The scalar-loss engine must compile to materially less temp memory
    than a loss over pipeline-gathered full-batch logits (the v1 design):
    the (B, S, vocab) fp32 logits buffer and the psum'd hidden buffer are
    gone (VERDICT r1 weak #4)."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    cfg = _tiny_cfg(vocab_size=2048, num_layers=4)  # big vocab -> logits dominate
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 2047, (8, 32)))
    labels = jnp.asarray(np.random.RandomState(1).randint(0, 2047, (8, 32)))
    pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=4, remat=True)
    st = ps.initialize_model_parallel(pipeline_model_parallel_size=4)
    params = pm.init(jax.random.PRNGKey(2), ids)
    sharded = jax.device_put(params, specs_to_shardings(pm.param_specs(ids), st.mesh))

    def v2_loss(p):
        return jax.grad(lambda p: pm.loss(p, ids, labels))(p)

    def v1_loss(p):
        return jax.grad(
            lambda p: parallel_cross_entropy_mean(pm.apply(p, ids), labels,
                                                  ignore_index=-100)
        )(p)

    with jax.set_mesh(st.mesh):
        m2 = jax.jit(v2_loss).lower(sharded).compile().memory_analysis()
        m1 = jax.jit(v1_loss).lower(sharded).compile().memory_analysis()
    if m1 is None or m2 is None:
        pytest.skip("backend provides no memory analysis")
    t1, t2 = m1.temp_size_in_bytes, m2.temp_size_in_bytes
    assert t2 < t1, f"scalar-loss temp {t2} not below logits-path temp {t1}"


# --- 1F1B engine (reference Train1F1BSchedule, scheduler.py:157) ------------

def test_1f1b_matches_dense_loss_and_grads():
    """The 1F1B engine's hand-written backward must reproduce dense autodiff
    exactly: loss AND every parameter gradient (embed on stage 0, all stacked
    layers, norm+head on the last stage)."""
    from neuronx_distributed_tpu.models.llama import rotary_embedding
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    cfg = _tiny_cfg(num_heads=2, num_kv_heads=2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 127)
    pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=4, remat=False,
                        schedule="1f1b")
    params = pm.init(jax.random.PRNGKey(2), ids)

    def dense_loss(p):
        x = pm._embed.apply({"params": p["embed"]}, ids)
        cos, sin = rotary_embedding(jnp.arange(16), cfg.head_dim_,
                                    cfg.rope_theta, dtype=x.dtype)
        x = pm._stage_fn(p["layers"]["block"], x, cos, sin)
        x = pm._norm.apply({"params": p["final_norm"]}, x)
        logits = pm._head.apply({"params": p["lm_head"]}, x)
        per = parallel_cross_entropy(logits, labels, ignore_index=-100)
        return per.sum() / (labels != -100).sum()

    golden_loss, golden_grads = jax.value_and_grad(dense_loss)(params)

    st = ps.initialize_model_parallel(pipeline_model_parallel_size=4)
    sharded = jax.device_put(params, specs_to_shardings(pm.param_specs(ids), st.mesh))
    with jax.set_mesh(st.mesh):
        # primal-only path (custom_vjp's undifferentiated branch)
        eval_loss = jax.jit(pm.loss)(sharded, ids, labels)
        # differentiated path (the combined 1F1B fwd+bwd scan)
        loss, grads = jax.jit(jax.value_and_grad(pm.loss))(sharded, ids, labels)
    assert abs(float(eval_loss) - float(golden_loss)) < 1e-5
    assert abs(float(loss) - float(golden_loss)) < 1e-5
    rel = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-8)),
        golden_grads, grads)
    worst = max(jax.tree.leaves(rel))
    assert worst < 1e-4, f"worst relative grad error {worst}"


def test_1f1b_train_step_pp_tp_dp():
    """1F1B composes with TP x DP + ZeRO-1 through the trainer surface."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )

    cfg = _tiny_cfg(num_layers=2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 127)
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2,
        optimizer_config={"zero_one_enabled": True},
    )
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 pipeline_model_parallel_size=2)
    pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=2, schedule="1f1b")
    model = pm.as_parallel_model(ids)
    opt = initialize_parallel_optimizer(nxd_config, model, learning_rate=1e-3)
    state = create_train_state(model, opt)
    step = make_train_step(model, opt, lambda p, b, r: pm.loss(p, b["ids"], b["labels"]))
    state, metrics = step(state, {"ids": ids, "labels": labels}, jax.random.key(0))
    l0 = float(metrics["loss"])
    state, metrics = step(state, {"ids": ids, "labels": labels}, jax.random.key(1))
    assert np.isfinite(l0) and float(metrics["loss"]) < l0  # it learns


def test_interleaved_1f1b_matches_dense_loss_and_grads():
    """The table-driven INTERLEAVED 1F1B engine (num_chunks > 1, reference
    TrainInterleavedSchedule scheduler.py:256-541) must reproduce dense
    autodiff: loss and every gradient, with the stacked grads coming back in
    the VPP layout (canonical re-order for the compare)."""
    from neuronx_distributed_tpu.models.llama import rotary_embedding
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    cfg = _tiny_cfg(num_layers=8)
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 127)
    pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=4, remat=False,
                        num_chunks=2, schedule="1f1b")
    st = ps.initialize_model_parallel(pipeline_model_parallel_size=2)
    params = pm.init(jax.random.PRNGKey(2), ids)

    def dense_loss(canon_params):
        x = pm._embed.apply({"params": canon_params["embed"]}, ids)
        cos, sin = rotary_embedding(jnp.arange(16), cfg.head_dim_,
                                    cfg.rope_theta, dtype=x.dtype)
        x = pm._stage_fn(canon_params["layers"]["block"], x, cos, sin)
        x = pm._norm.apply({"params": canon_params["final_norm"]}, x)
        logits = pm._head.apply({"params": canon_params["lm_head"]}, x)
        return parallel_cross_entropy_mean(logits, labels, ignore_index=-100)

    canon = {**params, "layers": {"block": pm.canonical_layer_params(params)}}
    golden_loss, golden_grads = jax.value_and_grad(dense_loss)(canon)

    sharded = jax.device_put(params, specs_to_shardings(pm.param_specs(ids), st.mesh))
    with jax.set_mesh(st.mesh):
        eval_loss = jax.jit(pm.loss)(sharded, ids, labels)
        loss, grads = jax.jit(jax.value_and_grad(pm.loss))(sharded, ids, labels)
    assert abs(float(eval_loss) - float(golden_loss)) < 1e-5
    assert abs(float(loss) - float(golden_loss)) < 1e-5
    canon_grads = {**grads, "layers": {"block": pm.canonical_layer_params(grads)}}
    rel = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-8)),
        golden_grads, canon_grads)
    worst = max(jax.tree.leaves(rel))
    assert worst < 1e-4, f"worst relative grad error {worst}"


def test_interleaved_1f1b_train_step():
    """PP2 x chunks2 interleaved-1F1B end-to-end through the trainer."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )

    cfg = _tiny_cfg(num_layers=4)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 127)
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2,
        optimizer_config={"zero_one_enabled": True},
    )
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 pipeline_model_parallel_size=2)
    pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=2,
                        num_chunks=2, schedule="1f1b")
    model = pm.as_parallel_model(ids)
    opt = initialize_parallel_optimizer(nxd_config, model, learning_rate=1e-3)
    state = create_train_state(model, opt)
    step = make_train_step(model, opt, lambda p, b, r: pm.loss(p, b["ids"], b["labels"]))
    state, metrics = step(state, {"ids": ids, "labels": labels}, jax.random.key(0))
    l0 = float(metrics["loss"])
    state, metrics = step(state, {"ids": ids, "labels": labels}, jax.random.key(1))
    assert np.isfinite(l0) and float(metrics["loss"]) < l0


def test_interleaved_1f1b_activation_memory_flat_in_microbatches():
    """VERDICT r3 weak #5 / missing #2: the interleaved engine needs the same
    memory bound 1F1B has. The table-driven interleaved-1F1B stash is sized
    by the schedule's peak (flat in mb); the gpipe-interleaved engine stores
    one chunk input per tick (linear in mb)."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    def temp_bytes(schedule, mb):
        B = 2 * mb
        cfg = _tiny_cfg(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_heads=2, num_kv_heads=2, num_layers=8)
        ids = jnp.zeros((B, 32), jnp.int32)
        labels = jnp.zeros((B, 32), jnp.int32)
        pm = PipelinedLlama(cfg, num_stages=2, num_microbatches=mb,
                            remat=True, num_chunks=2, schedule=schedule)
        if ps.model_parallel_is_initialized():
            ps.destroy_model_parallel()
        st = ps.initialize_model_parallel(pipeline_model_parallel_size=2)
        abstract = jax.eval_shape(lambda: pm.init(jax.random.PRNGKey(0), ids))
        sh = specs_to_shardings(pm.param_specs(ids), st.mesh)
        args = jax.tree.map(
            lambda s, x: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=x),
            abstract, sh)
        with jax.set_mesh(st.mesh):
            compiled = jax.jit(
                jax.grad(lambda p: pm.loss(p, ids, labels))).lower(args).compile()
        m = compiled.memory_analysis()
        if m is None:
            pytest.skip("backend provides no memory analysis")
        return m.temp_size_in_bytes

    t1_small, t1_big = temp_bytes("1f1b", 4), temp_bytes("1f1b", 16)
    tg_small, tg_big = temp_bytes("gpipe", 4), temp_bytes("gpipe", 16)
    grow_1f1b, grow_gpipe = t1_big - t1_small, tg_big - tg_small
    assert grow_gpipe > 0
    assert grow_1f1b < 0.2 * grow_gpipe, (
        f"interleaved-1f1b activation memory grew with microbatches: "
        f"{grow_1f1b} vs gpipe-interleaved {grow_gpipe}")


def test_1f1b_activation_memory_flat_in_microbatches():
    """THE 1F1B property: activation footprint is bounded by the fixed 2*pp
    stash — independent of microbatch count — while the GPipe-shaped engine
    grows linearly (VERDICT r2 missing #2). Measured at fixed microbatch
    SIZE (B = 2*mb) so per-tick work is constant."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    def temp_bytes(schedule, mb):
        B = 2 * mb
        cfg = _tiny_cfg(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_heads=2, num_kv_heads=2)
        ids = jnp.zeros((B, 32), jnp.int32)
        labels = jnp.zeros((B, 32), jnp.int32)
        pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=mb,
                            remat=True, schedule=schedule)
        if ps.model_parallel_is_initialized():
            ps.destroy_model_parallel()
        st = ps.initialize_model_parallel(pipeline_model_parallel_size=4)
        abstract = jax.eval_shape(lambda: pm.init(jax.random.PRNGKey(0), ids))
        sh = specs_to_shardings(pm.param_specs(ids), st.mesh)
        args = jax.tree.map(
            lambda s, x: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=x),
            abstract, sh)
        with jax.set_mesh(st.mesh):
            compiled = jax.jit(
                jax.grad(lambda p: pm.loss(p, ids, labels))).lower(args).compile()
        m = compiled.memory_analysis()
        if m is None:
            pytest.skip("backend provides no memory analysis")
        return m.temp_size_in_bytes

    t1_small, t1_big = temp_bytes("1f1b", 8), temp_bytes("1f1b", 32)
    tg_small, tg_big = temp_bytes("gpipe", 8), temp_bytes("gpipe", 32)
    # gpipe stores one stage input per tick: 4x the microbatches adds
    # ~3*mb*row_act bytes; 1f1b's stash is fixed, so its growth must be a
    # small fraction of gpipe's (ids/labels buffers only)
    grow_1f1b, grow_gpipe = t1_big - t1_small, tg_big - tg_small
    assert grow_gpipe > 0
    assert grow_1f1b < 0.1 * grow_gpipe, (
        f"1f1b activation memory grew with microbatches: {grow_1f1b} vs gpipe {grow_gpipe}")
    # and at every size the 1F1B program is strictly smaller
    assert t1_small < tg_small and t1_big < tg_big


def test_interleaved_1f1b_pp4_matches_dense_loss_and_grads():
    """VERDICT r4 next #8: an ENGINE execution above pp2. pp=4 x chunks=2
    (8 virtual stages, the deepest factoring 8 devices admit) through the
    table-driven interleaved-1F1B combined pass, loss + every grad vs dense
    autodiff — certifies the pp4 schedule table, vpp layer order, and the
    4-hop forward/reverse ppermute rings in execution, not just as tables."""
    from neuronx_distributed_tpu.models.llama import rotary_embedding
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean
    from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

    cfg = _tiny_cfg(num_layers=8)
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 127)
    pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=8, remat=False,
                        num_chunks=2, schedule="1f1b")
    st = ps.initialize_model_parallel(pipeline_model_parallel_size=4)
    params = pm.init(jax.random.PRNGKey(2), ids)

    def dense_loss(canon_params):
        x = pm._embed.apply({"params": canon_params["embed"]}, ids)
        cos, sin = rotary_embedding(jnp.arange(16), cfg.head_dim_,
                                    cfg.rope_theta, dtype=x.dtype)
        x = pm._stage_fn(canon_params["layers"]["block"], x, cos, sin)
        x = pm._norm.apply({"params": canon_params["final_norm"]}, x)
        logits = pm._head.apply({"params": canon_params["lm_head"]}, x)
        return parallel_cross_entropy_mean(logits, labels, ignore_index=-100)

    canon = {**params, "layers": {"block": pm.canonical_layer_params(params)}}
    golden_loss, golden_grads = jax.value_and_grad(dense_loss)(canon)

    sharded = jax.device_put(params, specs_to_shardings(pm.param_specs(ids), st.mesh))
    with jax.set_mesh(st.mesh):
        loss, grads = jax.jit(jax.value_and_grad(pm.loss))(sharded, ids, labels)
    assert abs(float(loss) - float(golden_loss)) < 1e-5
    canon_grads = {**grads, "layers": {"block": pm.canonical_layer_params(grads)}}
    rel = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-8)),
        golden_grads, canon_grads)
    worst = max(jax.tree.leaves(rel))
    assert worst < 1e-4, f"worst relative grad error {worst}"


def test_interleaved_1f1b_train_step_pp4_tp2():
    """pp4 x tp2 (the full 8-device mesh) interleaved-1F1B end-to-end
    through the trainer with ZeRO-1 — the deepest mixed factoring below the
    64-device tp8 x pp8 dryrun tier."""
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )

    cfg = _tiny_cfg(num_layers=8)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 127)
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 127)
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=4,
        optimizer_config={"zero_one_enabled": True},
    )
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 pipeline_model_parallel_size=4)
    pm = PipelinedLlama(cfg, num_stages=4, num_microbatches=4,
                        num_chunks=2, schedule="1f1b")
    model = pm.as_parallel_model(ids)
    opt = initialize_parallel_optimizer(nxd_config, model, learning_rate=1e-3)
    state = create_train_state(model, opt)
    step = make_train_step(model, opt, lambda p, b, r: pm.loss(p, b["ids"], b["labels"]))
    state, metrics = step(state, {"ids": ids, "labels": labels}, jax.random.key(0))
    l0 = float(metrics["loss"])
    state, metrics = step(state, {"ids": ids, "labels": labels}, jax.random.key(1))
    assert np.isfinite(l0) and float(metrics["loss"]) < l0


def test_the_pipelined_weights_program_is_one_text_for_every_seed(monkeypatch):
    """``as_parallel_model`` hands the key and the ids to its weights' program
    as arguments (ISSUE 57, as ``initialize_parallel_model`` does): two seeds
    lower to one text, and a seed draws what the closed-over form drew."""
    from neuronx_distributed_tpu.models import llama_pipeline
    from neuronx_distributed_tpu.models.llama_pipeline import PipelinedLlama

    texts, jit = [], jax.jit

    def recording(fn, **kw):
        jitted = jit(fn, **kw)

        def call(*args):
            texts.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    ps.initialize_model_parallel(tensor_model_parallel_size=2, pipeline_model_parallel_size=2)
    pm = PipelinedLlama(_tiny_cfg(), num_stages=2, num_microbatches=2)
    ids = np.random.RandomState(0).randint(0, 127, (4, 16)).astype(np.int32)
    monkeypatch.setattr(llama_pipeline.jax, "jit", recording)
    first = pm.as_parallel_model(ids, seed=1)
    second = pm.as_parallel_model(ids + 1, seed=2)
    monkeypatch.undo()
    assert len(texts) == 2 and texts[0] == texts[1]
    oracle = jax.jit(lambda: pm.init(jax.random.key(1), ids),
                     out_shardings=first.param_shardings())()
    got, want = jax.tree.leaves(first.params), jax.tree.leaves(oracle)
    assert len(got) == len(want) > 4
    for a, b in zip(got, want):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(got, jax.tree.leaves(second.params)))
