"""What a model's test file starts from, so that it copies none of it: a world,
seeded host weights, the full forward, the serving ``CausalLM`` (and ONE
compiled one a file for the tests that plant nothing in it), teacher-forced
logits through the cache, and the one process-wide memo of what was built.

A plain module, imported (``from tests import tiny``); a file binds its model
class once (``serving_lm = functools.partial(tiny.serving_lm, Cls, cfg=...)``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax.core import meta

from neuronx_distributed_tpu.inference import CausalLM
from neuronx_distributed_tpu.parallel import mesh

# three prompts over a vocabulary of 512, cut at LENS, then STEPS decoded tokens
IDS = np.random.RandomState(0).randint(1, 512, (3, 24)).astype(np.int32)
LENS = np.asarray([18, 12, 15])
STEPS = 6

_BUILT = {}


def built(key, make):
    """``make()`` once a process, whichever file or test asks first: weights,
    a ``CausalLM``, a compiled program. A worker runs a file's cases one after
    another (``conftest.py`` deals by file), so a worker builds a key once."""
    if key not in _BUILT:
        _BUILT[key] = make()
    return _BUILT[key]


def world(tp=1):
    mesh.destroy_model_parallel()
    mesh.initialize_model_parallel(tensor_model_parallel_size=tp, devices=jax.devices()[:tp])


def distance(got, want, scale=None):
    """The largest difference over the reference's largest value (or ``scale``)."""
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / (scale or np.abs(want).max()))


def noise(name, a):
    return jax.random.normal(jax.random.key(len(name)), a.shape)


def shake_norms(name, a):
    """A norm's scale times a seeded factor near one: a scale applied to the
    wrong axis, or left out, then shows."""
    return a * (1.0 + 0.3 * noise(name, a)) if "norm" in name else a


def make_params(model_cls, cfg, ids=np.zeros((1, 8), np.int32), shake=None, seed=1):
    """Host parameters of ``cfg``: the program's initialisers from a fixed key,
    then ``shake(name, leaf)`` over every leaf, as ONE compiled program (an
    eager flax ``init`` runs two passes of every scan a primitive at a time)."""
    def init(key, ids):
        params = meta.unbox(model_cls(cfg).init(key, ids))["params"]
        if shake is None:
            return params
        return jax.tree_util.tree_map_with_path(
            lambda path, a: shake(jax.tree_util.keystr(path), a), params)

    return jax.tree.map(np.asarray, jax.jit(init)(jax.random.key(seed), jnp.asarray(ids)))


def full_forward(model_cls, cfg, params, ids=IDS):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(model_cls(cfg).apply)({"params": params}, jnp.asarray(ids)))


def serving_lm(model_cls, params, cfg, moe_mode="capacity_factor", **kw):
    """Four slots behind pages of 8 and one bucket of 32 unless ``kw`` says
    otherwise; expert layers dispatch by capacity, as a served model's do."""
    if hasattr(cfg, "moe_mode"):
        cfg = dataclasses.replace(cfg, moe_mode=moe_mode)
    return CausalLM(cfg, params, model_cls, **{**dict(buckets=(32,), max_batch=4, page_size=8), **kw})


def compiled_lm(serving_lm, params, cache="paged"):
    """A file's ``serving_lm(params)`` behind pages of 8 or the slab, compiled,
    once a process: for every test that plants nothing in its programs (one
    that does builds its own)."""
    return built((serving_lm, cache), lambda: serving_lm(
        params, page_size=8 if cache == "paged" else None).compile())


def padded(ids, rows, lens):
    """Rows ``rows`` of ``ids`` cut at ``lens``, zero-padded to the longest."""
    prompts = np.zeros((len(rows), int(max(lens))), np.int32)
    for i, (r, n) in enumerate(zip(rows, lens)):
        prompts[i, :n] = ids[r, :n]
    return prompts


def cached_logits(lm, ids=IDS, lens=LENS, steps=STEPS, session=None):
    """Prefill of ``ids[:, :lens]`` then ``steps`` teacher-forced decode steps
    through the cache: logits ``(steps + 1, rows, vocab)``."""
    rows = np.arange(len(lens))
    session = session or lm.start_session()
    kw = dict(reserve_tokens=steps + 1) if lm.paged else {}
    with jax.default_matmul_precision("highest"):
        got = [np.asarray(lm.insert(session, rows, padded(ids, rows, lens), lengths=lens, **kw))]
        for t in range(steps):
            tok = np.zeros((lm.max_batch,), np.int32)
            tok[rows] = ids[rows, lens + t]
            got.append(np.asarray(lm.step(session, tok))[rows])
    return np.stack(got)


def at_cached(want, lens=LENS, steps=STEPS):
    """The reference's logits at the positions ``cached_logits`` answers."""
    pick = np.asarray(lens)[:, None] - 1 + np.arange(steps + 1)[None, :]
    return want[np.arange(len(lens))[:, None], pick].transpose(1, 0, 2)
