"""Model-generic HF ↔ framework checkpoint conversion.

Reference ``scripts/checkpoint_converter.py`` (``CheckpointConverterBase``:20)
is family-generic: one base class handles the rename/fuse/split mechanics and
per-model subclasses supply key maps (Llama, Mixtral expert stacking, NeoX
fused-QKV layout, BERT). Same shape here: :data:`FAMILIES` maps a family name
to (config builder, hf→nxd, nxd→hf); the mechanics (torch (out,in)
transposes, scan-axis layer stacking, GQA compact K/V) live in the per-family
functions below. TP/PP splitting never appears — the framework's params are
one global pytree laid out by GSPMD (see converters/hf_llama.py notes).

Family-specific layouts handled:

* **llama** — delegated to :mod:`converters.hf_llama` (incl. fused-QKV).
* **mixtral** — expert stacking: HF stores each expert's w1/w2/w3 as
  separate 2D matrices; the framework's ``ExpertMLPs`` holds fused 3D
  ``(E, H, I)`` tensors sharded ``(ep, None, tp)`` (reference
  ``convert_full_state_to_tp`` stacks the same way for its fused
  ``expert_mlps`` module).
* **olmoe** — Mixtral's stacking under OLMoE's key names
  (``mlp.gate``, ``mlp.experts.{e}.{gate,up,down}_proj``) plus the flat
  QK-norm scales ``self_attn.{q,k}_norm.weight``.
* **gpt_neox** — HF NeoX fuses QKV **head-interleaved**:
  ``query_key_value.weight`` is ``(N·3·D, H)`` ordered ``[q_h, k_h, v_h]``
  per head ``h`` — NOT ``[Q; K; V]`` blocks. Biases everywhere, biased
  LayerNorms.
* **bert** — encoder stack + MLM/NSP heads (``cls.predictions`` /
  ``cls.seq_relationship``), MLM decoder tied to word embeddings.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Callable, Dict, NamedTuple, Optional

import numpy as np

from neuronx_distributed_tpu.converters.hf_llama import (
    _np,
    config_from_hf as llama_config_from_hf,
    hf_to_nxd_llama,
    load_hf_safetensors,
    nxd_to_hf_llama,
    save_hf_safetensors,
)

PyTree = Any


def _read_hf_config(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, "config.json") if os.path.isdir(path) else path) as f:
        return json.load(f)


def _to_jnp(params: PyTree, dtype) -> PyTree:
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x: jnp.asarray(x, dtype), params)


# --------------------------------------------------------------------- mixtral

def mixtral_config_from_hf(path: str):
    from neuronx_distributed_tpu.models.mixtral import MixtralConfig

    hc = _read_hf_config(path)
    return MixtralConfig(
        vocab_size=hc["vocab_size"],
        hidden_size=hc["hidden_size"],
        intermediate_size=hc["intermediate_size"],
        num_layers=hc["num_hidden_layers"],
        num_heads=hc["num_attention_heads"],
        num_kv_heads=hc.get("num_key_value_heads", hc["num_attention_heads"]),
        max_seq_len=hc.get("max_position_embeddings", 4096),
        rope_theta=hc.get("rope_theta", 1e6),
        rms_norm_eps=hc.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=hc.get("tie_word_embeddings", False),
        num_experts=hc["num_local_experts"],
        top_k=hc["num_experts_per_tok"],
    )


# HF key templates of a sparse-expert FFN: (router, one expert matrix, the
# HF names of our gate/up/down). One stacking routine serves both families.
_MIXTRAL_MOE = ("model.layers.{i}.block_sparse_moe.gate.weight",
                "model.layers.{i}.block_sparse_moe.experts.{e}.{w}.weight",
                (("w1", "gate"), ("w3", "up"), ("w2", "down")))
_OLMOE_MOE = ("model.layers.{i}.mlp.gate.weight",
              "model.layers.{i}.mlp.experts.{e}.{w}.weight",
              (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down")))


def _moe_from_hf(hf: Dict[str, np.ndarray], L: int, E: int, keys) -> PyTree:
    """Experts stacked to the fused 3D layout: (L, E, in, out) from L x E
    torch (out, in) matrices; the router (L, hidden, E)."""
    router, expert, names = keys
    return {
        "router": {"kernel": np.stack([_np(hf[router.format(i=i)]).T for i in range(L)])},
        "experts": {ours: np.stack([
            np.stack([_np(hf[expert.format(i=i, e=e, w=w)]).T for e in range(E)])
            for i in range(L)]) for w, ours in names},
    }


def _moe_to_hf(out: Dict[str, np.ndarray], moe: PyTree, L: int, E: int, keys, dtype) -> None:
    router, expert, names = keys
    for i in range(L):
        out[router.format(i=i)] = _np(moe["router"]["kernel"][i], dtype).T
        for e in range(E):
            for w, ours in names:
                out[expert.format(i=i, e=e, w=w)] = _np(moe["experts"][ours][i, e], dtype).T


def hf_to_nxd_mixtral(hf: Dict[str, np.ndarray], config,
                      dtype: Optional[Any] = None) -> PyTree:
    """Attention/embed/norm mapping as Llama; experts stacked to the fused 3D
    layout (reference checkpoint_converter.py Mixtral subclass role)."""
    cfg = config
    # reuse the Llama attention/embed mapping (MixtralConfig IS a LlamaConfig;
    # the dense-mlp keys are absent so hf_to_nxd_llama skips them)
    base = hf_to_nxd_llama(
        {k: v for k, v in hf.items() if "block_sparse_moe" not in k},
        cfg, dtype=np.float32)
    base["model"]["layers"]["block"]["moe"] = _moe_from_hf(
        hf, cfg.num_layers, cfg.num_experts, _MIXTRAL_MOE)
    return _to_jnp(base, dtype or cfg.param_dtype)


def nxd_to_hf_mixtral(params: PyTree, config, dtype: Any = np.float32) -> Dict[str, np.ndarray]:
    cfg = config
    out = nxd_to_hf_llama(_drop_moe(params), cfg, dtype=dtype)
    _moe_to_hf(out, params["model"]["layers"]["block"]["moe"], cfg.num_layers,
               cfg.num_experts, _MIXTRAL_MOE, dtype)
    return out


# ----------------------------------------------------------------------- olmoe

def olmoe_config_from_hf(path: str):
    from neuronx_distributed_tpu.models.olmoe import OlmoeConfig

    hc = _read_hf_config(path)
    return OlmoeConfig(
        vocab_size=hc["vocab_size"],
        hidden_size=hc["hidden_size"],
        intermediate_size=hc["intermediate_size"],
        num_layers=hc["num_hidden_layers"],
        num_heads=hc["num_attention_heads"],
        num_kv_heads=hc.get("num_key_value_heads", hc["num_attention_heads"]),
        max_seq_len=hc.get("max_position_embeddings", 4096),
        rope_theta=hc.get("rope_theta", 10000.0),
        rms_norm_eps=hc.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=hc.get("tie_word_embeddings", False),
        qkv_clip=hc.get("clip_qkv"),
        num_experts=hc["num_experts"],
        top_k=hc["num_experts_per_tok"],
        norm_topk_prob=hc.get("norm_topk_prob", False),
    )


_OLMOE_QK_NORM = (("q_norm", "model.layers.{i}.self_attn.q_norm.weight"),
                  ("k_norm", "model.layers.{i}.self_attn.k_norm.weight"))


def hf_to_nxd_olmoe(hf: Dict[str, np.ndarray], config,
                    dtype: Optional[Any] = None) -> PyTree:
    """Llama's attention/embed/norm mapping, the flat QK-norm scales beside
    the projections, and Mixtral's expert stacking under OLMoE's key names."""
    cfg = config
    base = hf_to_nxd_llama({k: v for k, v in hf.items() if ".mlp." not in k},
                           cfg, dtype=np.float32)
    block = base["model"]["layers"]["block"]
    for ours, key in _OLMOE_QK_NORM:
        block["attention"][ours] = np.stack(
            [_np(hf[key.format(i=i)]) for i in range(cfg.num_layers)])
    block["moe"] = _moe_from_hf(hf, cfg.num_layers, cfg.num_experts, _OLMOE_MOE)
    return _to_jnp(base, dtype or cfg.param_dtype)


def nxd_to_hf_olmoe(params: PyTree, config, dtype: Any = np.float32) -> Dict[str, np.ndarray]:
    cfg = config
    out = nxd_to_hf_llama(_drop_moe(params), cfg, dtype=dtype)
    block = params["model"]["layers"]["block"]
    for ours, key in _OLMOE_QK_NORM:
        for i in range(cfg.num_layers):
            out[key.format(i=i)] = _np(block["attention"][ours][i], dtype)
    _moe_to_hf(out, block["moe"], cfg.num_layers, cfg.num_experts, _OLMOE_MOE, dtype)
    return out


def _drop_moe(params: PyTree) -> PyTree:
    """Shallow copy with the moe subtree removed (the Llama inverse then
    skips the absent dense mlp)."""
    p = dict(params)
    p["model"] = dict(params["model"])
    p["model"]["layers"] = {"block": dict(params["model"]["layers"]["block"])}
    p["model"]["layers"]["block"].pop("moe", None)
    return p


# -------------------------------------------------------------------- gpt_neox

def neox_config_from_hf(path: str):
    from neuronx_distributed_tpu.models.gpt_neox import GPTNeoXConfig

    hc = _read_hf_config(path)
    return GPTNeoXConfig(
        vocab_size=hc["vocab_size"],
        hidden_size=hc["hidden_size"],
        intermediate_size=hc["intermediate_size"],
        num_layers=hc["num_hidden_layers"],
        num_heads=hc["num_attention_heads"],
        num_kv_heads=hc["num_attention_heads"],  # NeoX is MHA
        max_seq_len=hc.get("max_position_embeddings", 2048),
        rope_theta=hc.get("rotary_emb_base", 10000.0),
        rotary_pct=hc.get("rotary_pct", 0.25),
        use_parallel_residual=hc.get("use_parallel_residual", True),
        layer_norm_eps=hc.get("layer_norm_eps", 1e-5),
        tie_word_embeddings=hc.get("tie_word_embeddings", False),
    )


def hf_to_nxd_neox(hf: Dict[str, np.ndarray], config,
                   dtype: Optional[Any] = None) -> PyTree:
    cfg = config
    L, H = cfg.num_layers, cfg.hidden_size
    N, D = cfg.num_heads, cfg.head_dim_
    dt = dtype or cfg.param_dtype

    def qkv(i):
        # HF NeoX fused layout: (N*3*D, H), rows ordered per-head [q, k, v]
        w = _np(hf[f"gpt_neox.layers.{i}.attention.query_key_value.weight"])
        w = w.reshape(N, 3, D, H)
        b = _np(hf[f"gpt_neox.layers.{i}.attention.query_key_value.bias"]).reshape(N, 3, D)
        # ours: kernels (H, N, D), biases (N, D)
        return (w[:, 0].transpose(2, 0, 1), w[:, 1].transpose(2, 0, 1),
                w[:, 2].transpose(2, 0, 1), b[:, 0], b[:, 1], b[:, 2])

    qs, ks, vs, qb, kb, vb = zip(*(qkv(i) for i in range(L)))

    def t(i, name):
        return _np(hf[f"gpt_neox.layers.{i}.{name}.weight"]).T

    def b(i, name):
        return _np(hf[f"gpt_neox.layers.{i}.{name}.bias"])

    def stack(fn):
        return np.stack([fn(i) for i in range(L)])

    def ln(i, name):
        return {"ln": {"scale": _np(hf[f"gpt_neox.layers.{i}.{name}.weight"]),
                       "bias": _np(hf[f"gpt_neox.layers.{i}.{name}.bias"])}}

    def stack_ln(name):
        per = [ln(i, name) for i in range(L)]
        return {"ln": {k: np.stack([p["ln"][k] for p in per]) for k in ("scale", "bias")}}

    block = {
        "attention": {
            "qkv": {"q_kernel": np.stack(qs), "k_kernel": np.stack(ks),
                    "v_kernel": np.stack(vs), "q_bias": np.stack(qb),
                    "k_bias": np.stack(kb), "v_bias": np.stack(vb)},
            "o_proj": {"kernel": stack(lambda i: t(i, "attention.dense")),
                       "bias": stack(lambda i: b(i, "attention.dense"))},
        },
        "mlp": {
            "up": {"kernel": stack(lambda i: t(i, "mlp.dense_h_to_4h")),
                   "bias": stack(lambda i: b(i, "mlp.dense_h_to_4h"))},
            "down": {"kernel": stack(lambda i: t(i, "mlp.dense_4h_to_h")),
                     "bias": stack(lambda i: b(i, "mlp.dense_4h_to_h"))},
        },
        "input_norm": stack_ln("input_layernorm"),
        "post_attn_norm": stack_ln("post_attention_layernorm"),
    }
    params = {
        "model": {
            "embed": {"embedding": _np(hf["gpt_neox.embed_in.weight"])},
            "layers": {"block": block},
            "final_norm": {"ln": {"scale": _np(hf["gpt_neox.final_layer_norm.weight"]),
                                  "bias": _np(hf["gpt_neox.final_layer_norm.bias"])}},
        }
    }
    if not cfg.tie_word_embeddings:
        if "embed_out.weight" not in hf:
            raise KeyError(
                "gpt_neox checkpoint has tie_word_embeddings=False but no "
                "'embed_out.weight' — refusing to substitute the input "
                "embedding as the lm_head")
        params["lm_head"] = {"kernel": _np(hf["embed_out.weight"]).T}
    return _to_jnp(params, dt)


def nxd_to_hf_neox(params: PyTree, config, dtype: Any = np.float32) -> Dict[str, np.ndarray]:
    cfg = config
    L, H, N, D = cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim_
    blk = params["model"]["layers"]["block"]
    out = {
        "gpt_neox.embed_in.weight": _np(params["model"]["embed"]["embedding"], dtype),
        "gpt_neox.final_layer_norm.weight": _np(
            params["model"]["final_norm"]["ln"]["scale"], dtype),
        "gpt_neox.final_layer_norm.bias": _np(
            params["model"]["final_norm"]["ln"]["bias"], dtype),
    }
    if "lm_head" in params:
        out["embed_out.weight"] = _np(params["lm_head"]["kernel"], dtype).T
    for i in range(L):
        qkv = blk["attention"]["qkv"]
        w = np.stack([  # (N, 3, D, H) head-interleaved
            _np(qkv["q_kernel"][i], dtype).transpose(1, 2, 0),
            _np(qkv["k_kernel"][i], dtype).transpose(1, 2, 0),
            _np(qkv["v_kernel"][i], dtype).transpose(1, 2, 0),
        ], axis=1)
        out[f"gpt_neox.layers.{i}.attention.query_key_value.weight"] = w.reshape(N * 3 * D, H)
        bvec = np.stack([_np(qkv["q_bias"][i], dtype), _np(qkv["k_bias"][i], dtype),
                         _np(qkv["v_bias"][i], dtype)], axis=1)
        out[f"gpt_neox.layers.{i}.attention.query_key_value.bias"] = bvec.reshape(N * 3 * D)
        out[f"gpt_neox.layers.{i}.attention.dense.weight"] = _np(
            blk["attention"]["o_proj"]["kernel"][i], dtype).T
        out[f"gpt_neox.layers.{i}.attention.dense.bias"] = _np(
            blk["attention"]["o_proj"]["bias"][i], dtype)
        for hf_name, ours in (("dense_h_to_4h", "up"), ("dense_4h_to_h", "down")):
            out[f"gpt_neox.layers.{i}.mlp.{hf_name}.weight"] = _np(
                blk["mlp"][ours]["kernel"][i], dtype).T
            out[f"gpt_neox.layers.{i}.mlp.{hf_name}.bias"] = _np(
                blk["mlp"][ours]["bias"][i], dtype)
        for hf_name, ours in (("input_layernorm", "input_norm"),
                              ("post_attention_layernorm", "post_attn_norm")):
            out[f"gpt_neox.layers.{i}.{hf_name}.weight"] = _np(
                blk[ours]["ln"]["scale"][i], dtype)
            out[f"gpt_neox.layers.{i}.{hf_name}.bias"] = _np(
                blk[ours]["ln"]["bias"][i], dtype)
    return out


# ------------------------------------------------------------------------ bert

def bert_config_from_hf(path: str):
    from neuronx_distributed_tpu.models.bert import BertConfig

    hc = _read_hf_config(path)
    return BertConfig(
        vocab_size=hc["vocab_size"],
        hidden_size=hc["hidden_size"],
        intermediate_size=hc["intermediate_size"],
        num_layers=hc["num_hidden_layers"],
        num_heads=hc["num_attention_heads"],
        max_position_embeddings=hc.get("max_position_embeddings", 512),
        type_vocab_size=hc.get("type_vocab_size", 2),
        layer_norm_eps=hc.get("layer_norm_eps", 1e-12),
    )


def hf_to_nxd_bert(hf: Dict[str, np.ndarray], config,
                   dtype: Optional[Any] = None) -> PyTree:
    cfg = config
    L, H, N = cfg.num_layers, cfg.hidden_size, cfg.num_heads
    D = cfg.head_dim_
    dt = dtype or cfg.param_dtype

    def t(name):
        return _np(hf[name]).T

    def dense(name):
        return {"kernel": t(f"{name}.weight"), "bias": _np(hf[f"{name}.bias"])}

    def ln(name):
        return {"ln": {"scale": _np(hf[f"{name}.weight"]), "bias": _np(hf[f"{name}.bias"])}}

    def stack(fn):
        per = [fn(i) for i in range(L)]
        import jax

        return jax.tree.map(lambda *xs: np.stack(xs), *per)

    def layer(i):
        p = f"bert.encoder.layer.{i}"
        return {
            "attention": {
                "qkv": {
                    "q_kernel": t(f"{p}.attention.self.query.weight").reshape(H, N, D),
                    "k_kernel": t(f"{p}.attention.self.key.weight").reshape(H, N, D),
                    "v_kernel": t(f"{p}.attention.self.value.weight").reshape(H, N, D),
                    "q_bias": _np(hf[f"{p}.attention.self.query.bias"]).reshape(N, D),
                    "k_bias": _np(hf[f"{p}.attention.self.key.bias"]).reshape(N, D),
                    "v_bias": _np(hf[f"{p}.attention.self.value.bias"]).reshape(N, D),
                },
                "output": dense(f"{p}.attention.output.dense"),
            },
            "attention_norm": ln(f"{p}.attention.output.LayerNorm"),
            "intermediate": dense(f"{p}.intermediate.dense"),
            "mlp_output": dense(f"{p}.output.dense"),
            "output_norm": ln(f"{p}.output.LayerNorm"),
        }

    params = {
        "bert": {
            "word_embeddings": {"embedding": _np(hf["bert.embeddings.word_embeddings.weight"])},
            "position_embeddings": {"embedding": _np(hf["bert.embeddings.position_embeddings.weight"])},
            "token_type_embeddings": {"embedding": _np(hf["bert.embeddings.token_type_embeddings.weight"])},
            "embed_norm": ln("bert.embeddings.LayerNorm"),
            "layers": {"block": stack(layer)},
            "pooler": dense("bert.pooler.dense"),
        },
        "mlm_transform": dense("cls.predictions.transform.dense"),
        "mlm_norm": ln("cls.predictions.transform.LayerNorm"),
        "mlm_bias": _np(hf["cls.predictions.bias"]),
        "nsp_head": dense("cls.seq_relationship"),
    }
    return _to_jnp(params, dt)


def nxd_to_hf_bert(params: PyTree, config, dtype: Any = np.float32) -> Dict[str, np.ndarray]:
    cfg = config
    L, H, N, D = cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim_
    b = params["bert"]
    blk = b["layers"]["block"]

    def put_dense(out, name, tree):
        out[f"{name}.weight"] = _np(tree["kernel"], dtype).T
        out[f"{name}.bias"] = _np(tree["bias"], dtype)

    def put_dense_i(out, name, tree, i):
        out[f"{name}.weight"] = _np(tree["kernel"][i], dtype).T
        out[f"{name}.bias"] = _np(tree["bias"][i], dtype)

    def put_ln(out, name, tree, i=None):
        sel = (lambda x: x[i]) if i is not None else (lambda x: x)
        out[f"{name}.weight"] = _np(sel(tree["ln"]["scale"]), dtype)
        out[f"{name}.bias"] = _np(sel(tree["ln"]["bias"]), dtype)

    out: Dict[str, np.ndarray] = {
        "bert.embeddings.word_embeddings.weight": _np(b["word_embeddings"]["embedding"], dtype),
        "bert.embeddings.position_embeddings.weight": _np(b["position_embeddings"]["embedding"], dtype),
        "bert.embeddings.token_type_embeddings.weight": _np(b["token_type_embeddings"]["embedding"], dtype),
        "cls.predictions.bias": _np(params["mlm_bias"], dtype),
    }
    put_ln(out, "bert.embeddings.LayerNorm", b["embed_norm"])
    put_dense(out, "bert.pooler.dense", b["pooler"])
    put_dense(out, "cls.predictions.transform.dense", params["mlm_transform"])
    put_ln(out, "cls.predictions.transform.LayerNorm", params["mlm_norm"])
    put_dense(out, "cls.seq_relationship", params["nsp_head"])
    for i in range(L):
        p = f"bert.encoder.layer.{i}"
        qkv = blk["attention"]["qkv"]
        for nm in ("query", "key", "value"):
            c = nm[0]
            out[f"{p}.attention.self.{nm}.weight"] = _np(
                qkv[f"{c}_kernel"][i], dtype).reshape(H, N * D).T
            out[f"{p}.attention.self.{nm}.bias"] = _np(
                qkv[f"{c}_bias"][i], dtype).reshape(N * D)
        put_dense_i(out, f"{p}.attention.output.dense", blk["attention"]["output"], i)
        put_ln(out, f"{p}.attention.output.LayerNorm", blk["attention_norm"], i)
        put_dense_i(out, f"{p}.intermediate.dense", blk["intermediate"], i)
        put_dense_i(out, f"{p}.output.dense", blk["mlp_output"], i)
        put_ln(out, f"{p}.output.LayerNorm", blk["output_norm"], i)
    return out


# -------------------------------------------------------------------- dbrx

def dbrx_config_from_hf(path: str):
    """HF DbrxConfig nests attention/ffn settings under ``attn_config`` /
    ``ffn_config``; architecture = the MoE stack with bias-free LayerNorms
    and clipped QKV (models/mixtral.py dbrx preset)."""
    from neuronx_distributed_tpu.models.mixtral import MixtralConfig

    hc = _read_hf_config(path)
    attn = hc.get("attn_config", {}) or {}
    ffn = hc.get("ffn_config", {}) or {}
    return MixtralConfig(
        vocab_size=hc["vocab_size"], hidden_size=hc["d_model"],
        intermediate_size=ffn.get("ffn_hidden_size", 10752),
        num_layers=hc["n_layers"], num_heads=hc["n_heads"],
        num_kv_heads=attn.get("kv_n_heads", 8),
        rope_theta=attn.get("rope_theta", 5e5),
        num_experts=ffn.get("moe_num_experts", 16),
        top_k=ffn.get("moe_top_k", 4),
        max_seq_len=hc.get("max_seq_len", 2048),
        tie_word_embeddings=hc.get("tie_word_embeddings", False),
        norm_type="layernorm", norm_bias=False,
        qkv_clip=attn.get("clip_qkv"),
    )


def hf_to_nxd_dbrx(hf: Dict[str, np.ndarray], config,
                   dtype: Optional[Any] = None) -> PyTree:
    """DBRX HF layout (``transformer.blocks.*``): fused ``Wqkv`` in [Q;K;V]
    block order; experts PRE-FUSED as ``mlp.w1/v1/w2`` of shape (E*I, H) —
    HF's ``DbrxExpertGLU`` computes ``x @ w1[e].T`` (gate), ``x @ v1[e].T``
    (up), ``a @ w2[e]`` (down), so gate/up transpose to (E, H, I) and down
    stays (E, I, H); bias-free LayerNorms land under the ``ln`` submodule."""
    cfg = config
    L, E, H, I = cfg.num_layers, cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    N, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    dt = dtype or cfg.param_dtype

    def blk(i: int) -> str:
        return f"transformer.blocks.{i}"

    def qkv(i):
        w = _np(hf[f"{blk(i)}.norm_attn_norm.attn.Wqkv.weight"])  # (ND+2NkvD, H)
        q, k, v = np.split(w, [N * D, N * D + NKV * D], axis=0)
        return (q.T.reshape(H, N, D), k.T.reshape(H, NKV, D), v.T.reshape(H, NKV, D))

    qs, ks, vs = zip(*(qkv(i) for i in range(L)))
    stack = lambda f: np.stack([f(i) for i in range(L)])  # noqa: E731
    block = {
        "attention": {
            "qkv": {"q_kernel": np.stack(qs), "k_kernel": np.stack(ks),
                    "v_kernel": np.stack(vs)},
            "o_proj": {"kernel": stack(
                lambda i: _np(hf[f"{blk(i)}.norm_attn_norm.attn.out_proj.weight"]).T)},
        },
        "input_norm": {"ln": {"scale": stack(
            lambda i: _np(hf[f"{blk(i)}.norm_attn_norm.norm_1.weight"]))}},
        "post_attn_norm": {"ln": {"scale": stack(
            lambda i: _np(hf[f"{blk(i)}.norm_attn_norm.norm_2.weight"]))}},
        "moe": {
            "router": {"kernel": stack(
                lambda i: _np(hf[f"{blk(i)}.ffn.router.layer.weight"]).T)},
            "experts": {
                "gate": stack(lambda i: _np(
                    hf[f"{blk(i)}.ffn.experts.mlp.w1"]).reshape(E, I, H).transpose(0, 2, 1)),
                "up": stack(lambda i: _np(
                    hf[f"{blk(i)}.ffn.experts.mlp.v1"]).reshape(E, I, H).transpose(0, 2, 1)),
                "down": stack(lambda i: _np(
                    hf[f"{blk(i)}.ffn.experts.mlp.w2"]).reshape(E, I, H)),
            },
        },
    }
    params = {
        "model": {
            "embed": {"embedding": _np(hf["transformer.wte.weight"])},
            "layers": {"block": block},
            "final_norm": {"ln": {"scale": _np(hf["transformer.norm_f.weight"])}},
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": _np(hf["lm_head.weight"]).T}
    return _to_jnp(params, dt)


def nxd_to_hf_dbrx(params: PyTree, config, dtype: Any = np.float32) -> Dict[str, np.ndarray]:
    cfg = config
    L, E = cfg.num_layers, cfg.num_experts
    H, I = cfg.hidden_size, cfg.intermediate_size
    N, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    blk = params["model"]["layers"]["block"]
    out = {
        "transformer.wte.weight": _np(params["model"]["embed"]["embedding"], dtype),
        "transformer.norm_f.weight": _np(
            params["model"]["final_norm"]["ln"]["scale"], dtype),
    }
    if "lm_head" in params:
        out["lm_head.weight"] = _np(params["lm_head"]["kernel"], dtype).T
    for i in range(L):
        q = _np(blk["attention"]["qkv"]["q_kernel"][i], dtype).reshape(H, N * D).T
        k = _np(blk["attention"]["qkv"]["k_kernel"][i], dtype).reshape(H, NKV * D).T
        v = _np(blk["attention"]["qkv"]["v_kernel"][i], dtype).reshape(H, NKV * D).T
        b = f"transformer.blocks.{i}"
        out[f"{b}.norm_attn_norm.attn.Wqkv.weight"] = np.concatenate([q, k, v], axis=0)
        out[f"{b}.norm_attn_norm.attn.out_proj.weight"] = _np(
            blk["attention"]["o_proj"]["kernel"][i], dtype).T
        out[f"{b}.norm_attn_norm.norm_1.weight"] = _np(
            blk["input_norm"]["ln"]["scale"][i], dtype)
        out[f"{b}.norm_attn_norm.norm_2.weight"] = _np(
            blk["post_attn_norm"]["ln"]["scale"][i], dtype)
        out[f"{b}.ffn.router.layer.weight"] = _np(
            blk["moe"]["router"]["kernel"][i], dtype).T
        out[f"{b}.ffn.experts.mlp.w1"] = _np(
            blk["moe"]["experts"]["gate"][i], dtype).transpose(0, 2, 1).reshape(E * I, H)
        out[f"{b}.ffn.experts.mlp.v1"] = _np(
            blk["moe"]["experts"]["up"][i], dtype).transpose(0, 2, 1).reshape(E * I, H)
        out[f"{b}.ffn.experts.mlp.w2"] = _np(
            blk["moe"]["experts"]["down"][i], dtype).reshape(E * I, H)
    return out


# -------------------------------------------------------------------- registry

class Family(NamedTuple):
    config_from_hf: Callable[[str], Any]
    hf_to_nxd: Callable[..., PyTree]
    nxd_to_hf: Callable[..., Dict[str, np.ndarray]]


FAMILIES: Dict[str, Family] = {
    "llama": Family(llama_config_from_hf, hf_to_nxd_llama, nxd_to_hf_llama),
    "mixtral": Family(mixtral_config_from_hf, hf_to_nxd_mixtral, nxd_to_hf_mixtral),
    "olmoe": Family(olmoe_config_from_hf, hf_to_nxd_olmoe, nxd_to_hf_olmoe),
    "gpt_neox": Family(neox_config_from_hf, hf_to_nxd_neox, nxd_to_hf_neox),
    "bert": Family(bert_config_from_hf, hf_to_nxd_bert, nxd_to_hf_bert),
    "dbrx": Family(dbrx_config_from_hf, hf_to_nxd_dbrx, nxd_to_hf_dbrx),
}


def detect_family(hf_keys) -> str:
    """Infer the family from checkpoint key prefixes (reference's CLI takes
    --model_style; detection keeps the one-command UX)."""
    keys = list(hf_keys)
    if any("block_sparse_moe" in k for k in keys):
        return "mixtral"
    if any("norm_attn_norm" in k for k in keys):  # DBRX-unique submodule
        return "dbrx"
    if any(".mlp.experts." in k for k in keys) and any(".self_attn.q_norm." in k for k in keys):
        return "olmoe"
    if any(k.startswith("gpt_neox.") for k in keys):
        return "gpt_neox"
    if any(k.startswith("bert.") for k in keys):
        return "bert"
    if any(k.startswith("model.layers.") for k in keys):
        return "llama"
    raise ValueError(f"cannot infer model family from keys like {keys[:5]}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--input", required=True, help="HF dir/file, or framework ckpt dir")
    p.add_argument("--output", required=True)
    p.add_argument("--direction", choices=["hf2nxd", "nxd2hf"], default="hf2nxd")
    p.add_argument("--model", choices=[*FAMILIES, "auto"], default="auto")
    p.add_argument("--config", help="HF config.json (defaults to <input>/config.json)")
    p.add_argument("--tag", default=None)
    args = p.parse_args(argv)

    if args.direction == "hf2nxd":
        hf = load_hf_safetensors(args.input)
        family = detect_family(hf) if args.model == "auto" else args.model
        fam = FAMILIES[family]
        cfg = fam.config_from_hf(args.config or args.input)
        params = fam.hf_to_nxd(hf, cfg)
        from neuronx_distributed_tpu.checkpoint import save_checkpoint

        save_checkpoint(args.output, tag=args.tag or "converted", state=params,
                        async_save=False)
    else:
        if args.model == "auto":
            raise SystemExit("--direction nxd2hf requires an explicit --model")
        if not args.config:
            # --input is a framework checkpoint dir with no config.json;
            # without --config the failure would surface as an opaque
            # FileNotFoundError deep inside _read_hf_config
            raise SystemExit(
                "--direction nxd2hf requires --config pointing at the HF "
                "model dir (the framework checkpoint under --input has no "
                "config.json)")
        fam = FAMILIES[args.model]
        cfg = fam.config_from_hf(args.config)
        from neuronx_distributed_tpu.checkpoint import load_checkpoint

        state, _ = load_checkpoint(args.input, tag=args.tag)
        params = state.get("params", state) if isinstance(state, dict) else state.params
        save_hf_safetensors(fam.nxd_to_hf(params, cfg),
                            os.path.join(args.output, "model.safetensors"))


if __name__ == "__main__":
    main()
