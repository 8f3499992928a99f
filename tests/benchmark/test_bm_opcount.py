"""Operation and byte counts against numbers worked by hand."""

import json
from pathlib import Path

import pytest

from benchmark import opcount

CONFIGS = Path(opcount.__file__).resolve().parent / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_mixtral_by_hand():
    c = cfg("mixtral-8x7b")
    attn = 4096 * 4096 * 2 + 2 * 4096 * 1024          # q and o; k and v at 8 KV heads
    expert = 3 * 4096 * 14336
    assert opcount.attention_params(c) == attn == 41_943_040
    assert opcount.expert_params(c) == expert == 176_160_768
    assert opcount.layer_params(c) == attn + 4096 * 8 + 8 * expert == 1_451_261_952
    assert opcount.active_layer_params(c) == attn + 4096 * 8 + 2 * expert
    # published depth: 46.7 B parameters, 12.9 B active per token
    full = dict(c, num_hidden_layers=32)
    assert opcount.total_params(full) == pytest.approx(46.70e9, rel=2e-3)
    # a decode step over 8 rows reads all 8 experts: 3 layers + head, bf16
    need = opcount.decode_step_bytes(c, rows=8, context_tokens=0)
    assert need == (3 * 1_451_261_952 + 4096 * 32000) * 2
    # one row can reach only 2 experts
    one = opcount.decode_step_bytes(c, rows=1, context_tokens=0)
    assert one == (3 * (attn + 4096 * 8 + 2 * expert) + 4096 * 32000) * 2
    assert opcount.kv_bytes_per_token(c) == 3 * 2 * 8 * 128 * 2


def test_mistral_by_hand():
    c = cfg("mistral-7b-v0.3")
    layer = 41_943_040 + 3 * 4096 * 14336
    assert opcount.layer_params(c) == layer == 218_103_808
    assert opcount.total_params(dict(c, num_hidden_layers=32)) == pytest.approx(7.248e9, rel=1e-3)
    assert opcount.kv_bytes_per_token(c) == 65536                    # 64 KB a token at 16 layers
    # prefill of one 1000-token prompt, one logits row
    attn = 2 * 2 * 32 * 128 * 1000 * 1001 / 2
    assert opcount.prefill_flops(c, [1000]) == pytest.approx(
        16 * (2 * layer * 1000 + attn) + 2 * 4096 * 32768)
    # context bytes dominate the step once 8 streams hold 2000 tokens each
    step = opcount.decode_step_bytes(c, rows=8, context_tokens=16000)
    assert step == (16 * layer + 4096 * 32768) * 2 + 16000 * 65536


def test_pythia_by_hand():
    c = cfg("pythia-6.9b")
    layer = 4 * 4096 * 4096 + 2 * 4096 * 16384                        # MHA; GELU MLP: 2 matrices
    assert opcount.layer_params(c) == layer == 201_326_592
    assert opcount.total_params(dict(c, num_hidden_layers=32)) == pytest.approx(6.86e9, rel=2e-3)
    per_token_fwd = 8 * (2 * layer + 2 * 2 * 32 * 128 * 2049 / 2) + 2 * 4096 * 50432
    assert opcount.train_flops_per_token(c, 2048) == pytest.approx(3 * per_token_fwd)
    # 16384 tokens a step: about 190 TFLOP
    assert 16384 * opcount.train_flops_per_token(c, 2048) == pytest.approx(190e12, rel=0.05)
