import pytest

import costs

MISTRAL = {"hidden": 4096, "n_heads": 32, "n_kv_heads": 8, "ffn": 14336,
           "vocab_size": 32768, "n_layers": 8}


def test_mistral_layer_and_model_sizes_from_the_issue():
    # attention 41.9 M + SwiGLU 176.2 M (+ 2 norms) = 218.1 M a layer
    assert 4096 * 4096 * 2 + 2 * 4096 * 1024 == 41_943_040
    assert 3 * 4096 * 14336 == 176_160_768
    assert costs.llama_layer_params(MISTRAL) == 41_943_040 + 176_160_768 + 8192
    assert costs.llama_params(MISTRAL) / 1e9 == pytest.approx(2.013, abs=1e-3)
    assert costs.llama_params({**MISTRAL, "n_layers": 32}) / 1e9 == pytest.approx(7.248, abs=1e-3)
    assert costs.llama_kv_bytes_per_token(MISTRAL) == 32 * 1024  # 32 KB a token


def test_decode_step_bytes():
    # 8 layers + the head, bf16: 3.76 GB before any KV
    assert costs.llama_decode_step_bytes(MISTRAL, 0) / 1e9 == pytest.approx(3.758, abs=1e-3)
    with_kv = costs.llama_decode_step_bytes(MISTRAL, 32 * 168)
    assert with_kv - costs.llama_decode_step_bytes(MISTRAL, 0) == 32 * 168 * 32768


def test_bert_step_flops():
    base = {"hidden": 768, "ffn": 3072, "n_layers": 12, "n_classes": 2}
    # per token per layer: 4 h^2 + 2 h ffn multiply-adds, plus attention 2 L h
    per_token_layer = 2 * (4 * 768**2 + 2 * 768 * 3072) + 2 * 2 * 128 * 768
    want = 12 * 256 * 128 * per_token_layer + 2 * 256 * 768 * 768 + 2 * 256 * 768 * 2
    assert costs.bert_forward_flops(base, 256, 128) == want
    assert want / 1e12 == pytest.approx(5.72, abs=0.01)
