"""What a step needs, computed from shapes: parameters, bytes a decode step
must read, FLOPs of a BERT forward.  The algorithm's counts, not the
compiler's (``cost_analysis()`` counts what XLA emitted, padding and
recomputation included, so it is no base for a roofline share)."""

from __future__ import annotations


def llama_layer_params(c: dict) -> int:
    """Parameters of one decoder layer (GQA attention + SwiGLU + 2 norms)."""
    h, nh, kv, d, f = (
        c["hidden"], c["n_heads"], c["n_kv_heads"],
        c["hidden"] // c["n_heads"], c["ffn"],
    )
    attn = h * nh * d + 2 * h * kv * d + nh * d * h
    mlp = 3 * h * f
    return attn + mlp + 2 * h


def llama_params(c: dict) -> int:
    """All parameters: layers, both embeddings (untied head), final norm."""
    return (
        c["n_layers"] * llama_layer_params(c)
        + 2 * c["vocab_size"] * c["hidden"] + c["hidden"]
    )


def llama_kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    d = c["hidden"] // c["n_heads"]
    return c["n_layers"] * 2 * c["n_kv_heads"] * d * itemsize


def llama_decode_step_bytes(
    c: dict, tokens_in_cache: float, itemsize: int = 2
) -> float:
    """Bytes one decode step has to read from HBM: every layer's weights and
    the output head once (the batch shares them), and the keys and values of
    the tokens the step attends to.  The embedding rows of the batch, the
    activations and the KV written are left out: under 0.1 % at these
    sizes."""
    weights = (
        c["n_layers"] * llama_layer_params(c)
        + c["vocab_size"] * c["hidden"] + c["hidden"]
    ) * itemsize
    return weights + tokens_in_cache * llama_kv_bytes_per_token(c, itemsize)


def bert_forward_flops(c: dict, rows: int, seq: int) -> float:
    """FLOPs of one BERT forward over ``rows`` sequences of ``seq`` tokens
    (padding included: the program computes every position), two per
    multiply-add: the projections, the attention scores and their product
    with V, the two feed-forward layers, per layer; the pooler and the
    classifier on the first token.  Softmax, layer norms and GELU are left
    out (not matrix work)."""
    h, f, n_layers = c["hidden"], c["ffn"], c["n_layers"]
    tokens = rows * seq
    per_layer = (
        2 * tokens * h * h * 4          # query, key, value, out
        + 2 * 2 * rows * seq * seq * h  # scores and probs @ V, all heads
        + 2 * 2 * tokens * h * f        # ffn up and down
    )
    head = 2 * rows * h * h + 2 * rows * h * c["n_classes"]
    return float(n_layers * per_layer + head)
