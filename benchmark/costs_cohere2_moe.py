"""What a step of the Cohere2-MoE share needs, computed from shapes: the
parameters of a layer, the bytes a decode step must read given the experts
its tokens touched and the K/V inside each layer's window, the FLOPs of a
prefill.  The algorithm's counts, not the compiler's.  ``c`` is a
configuration's ``graph.parameters`` (``models/cohere2_moe.py::Config``)."""

from __future__ import annotations


def held(c: dict) -> int:
    """Routed experts this share holds (``experts_held = "first:count"``)."""
    text = str(c.get("experts_held") or "")
    return int(text.partition(":")[2]) if text else int(c["n_experts"])


def expert_params(c: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * c["hidden"] * c["ffn"]


def attention_params(c: dict) -> int:
    h, nh, kv, d = c["hidden"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return 2 * h * nh * d + 2 * h * kv * d


def dense_layer_params(c: dict) -> int:
    """What every token's step reads of a layer whatever the routing:
    attention, the shared experts, the router, the one norm."""
    return (
        attention_params(c) + c["n_shared_experts"] * expert_params(c)
        + c["hidden"] * c["n_experts"] + c["hidden"]
    )


def layer_params(c: dict) -> int:
    """A layer as this share holds it."""
    return dense_layer_params(c) + held(c) * expert_params(c)


def share_params(c: dict) -> int:
    """All parameters here: the layers, the tied embedding's rows, the
    final norm."""
    return (
        c["n_layers"] * layer_params(c) + c["vocab_size"] * c["hidden"]
        + c["hidden"]
    )


def kv_bytes_per_token_layer(c: dict, itemsize: int = 2) -> int:
    return 2 * c["n_kv_heads"] * c["head_dim"] * itemsize


def full_layers(c: dict) -> int:
    return c["n_layers"] // c["layer_pattern"]


def kv_tokens_read(c: dict, contexts: list[float]) -> float:
    """Token-layers of K/V a decode step has to read for slots at
    ``contexts``: a full layer all of a slot's context, a sliding layer no
    more than its window."""
    n_full = full_layers(c)
    n_win = c["n_layers"] - n_full
    w = c["sliding_window"]
    return sum(n_full * t + n_win * min(t, w) for t in contexts)


def decode_step_bytes(
    c: dict, contexts: list[float], experts_touched_per_step: float,
    itemsize: int = 2,
) -> float:
    """Bytes one decode step has to read from HBM: the dense part of every
    layer and the head (the vocabulary slice, once: the batch shares it),
    the experts the step's tokens touched (``experts_touched_per_step``:
    summed over the layers, from the program's counter — not all that are
    held, so a program that skips untouched experts cannot read over 100 %),
    and the K/V inside each layer's window.  Activations, the K/V written
    and the embedding rows gathered are left out: under 0.1 %."""
    weights = (
        c["n_layers"] * dense_layer_params(c)
        + c["vocab_size"] * c["hidden"] + c["hidden"]
        + experts_touched_per_step * expert_params(c)
    ) * itemsize
    return weights + kv_tokens_read(c, contexts) * kv_bytes_per_token_layer(c, itemsize)


def prefill_flops(c: dict, tokens: int) -> float:
    """FLOPs of one prompt of ``tokens`` through this share, two per
    multiply-add: the projections, the scores and their product with V
    inside each layer's window, the shared experts, the router, and the
    routed experts at the expected ``experts_per_tok * held / n_experts``
    pairs a token (the counter has the true count); the head at the last
    position only.  Softmax, norms and activations are left out."""
    h, d, nh = c["hidden"], c["head_dim"], c["n_heads"]
    w = c["sliding_window"]
    n_full = full_layers(c)
    n_win = c["n_layers"] - n_full
    causal_pairs = tokens * (tokens + 1) / 2
    if tokens > w:
        window_pairs = w * (w + 1) / 2 + (tokens - w) * w
    else:
        window_pairs = causal_pairs
    scores = 2 * 2 * nh * d * (n_full * causal_pairs + n_win * window_pairs)
    pairs_per_token = c["experts_per_tok"] * held(c) / c["n_experts"]
    per_token = 2 * (
        attention_params(c) + c["n_shared_experts"] * expert_params(c)
        + h * c["n_experts"] + pairs_per_token * expert_params(c)
    )
    return float(
        c["n_layers"] * tokens * per_token + scores + 2 * h * c["vocab_size"]
    )
