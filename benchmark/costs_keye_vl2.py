"""What a step of the Keye-VL-2.0 decoder needs, computed from shapes: the
parameters of a layer, the bytes a decode step must read given the experts
its tokens touched and the keys it scored and selected, the FLOPs of a
prompt.  The algorithm's counts, not the compiler's.  ``c`` is a
configuration's ``graph.parameters`` (``models/keye_vl2.py::Config``)."""

from __future__ import annotations


def held(c: dict) -> int:
    """Routed experts held here (``experts_held = "first:count"``)."""
    text = str(c.get("experts_held") or "")
    return int(text.partition(":")[2]) if text else int(c["n_experts"])


def expert_params(c: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * c["hidden"] * c["ffn"]


def attention_params(c: dict) -> int:
    h, nh, kv, d = c["hidden"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return 2 * h * nh * d + 2 * h * kv * d


def indexer_params(c: dict) -> int:
    """Index queries, the one index key, the head weights."""
    h, hi, di = c["hidden"], c["index_heads"], c["index_dim"]
    return h * hi * di + h * di + h * hi


def router_params(c: dict) -> int:
    return c["hidden"] * c["n_experts"]


def norm_params(c: dict) -> int:
    """Two RMSNorms, the q and k head norms, the index key's LayerNorm."""
    return 2 * c["hidden"] + 2 * c["head_dim"] + 2 * c["index_dim"]


def dense_layer_params(c: dict) -> int:
    """What every token's step reads of a layer whatever the routing."""
    return (
        attention_params(c) + indexer_params(c) + router_params(c)
        + norm_params(c)
    )


def layer_params(c: dict) -> int:
    return dense_layer_params(c) + held(c) * expert_params(c)


def head_params(c: dict) -> int:
    """The untied head and the final norm (a step reads both whole)."""
    return c["vocab_size"] * c["hidden"] + c["hidden"]


def model_params(c: dict) -> int:
    """All parameters here: the layers, the embedding, the head."""
    return (
        c["n_layers"] * layer_params(c) + c["vocab_size"] * c["hidden"]
        + head_params(c)
    )


def pool_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """K, V and the index key of one token on every layer."""
    return c["n_layers"] * (kv_row_bytes(c, itemsize) + index_key_bytes(c, itemsize))


def kv_row_bytes(c: dict, itemsize: int = 2) -> int:
    """K and V of one token on one layer: what a selected key costs."""
    return 2 * c["n_kv_heads"] * c["head_dim"] * itemsize


def index_key_bytes(c: dict, itemsize: int = 2) -> int:
    """One token's index key on one layer: what a scored key costs."""
    return c["index_dim"] * itemsize


def decode_step_bytes(
    c: dict, experts_touched_per_step: float, keys_scored_per_step: float,
    keys_selected_per_step: float, itemsize: int = 2,
) -> float:
    """Bytes one decode step has to read from HBM whatever implements it:
    every layer's attention, indexer, router and norms; the head; the
    experts the step's tokens TOUCHED (summed over the layers, from the
    program's counter: not all that are held, so a program that skips
    untouched experts cannot read over 100 %); an index key for every key
    scored and a K/V row for every key selected (both summed over layers
    and slots, from the counters).  Activations, what is written and the
    embedding rows gathered are left out: under 0.1 %."""
    weights = (
        c["n_layers"] * dense_layer_params(c) + head_params(c)
        + experts_touched_per_step * expert_params(c)
    ) * itemsize
    return (
        weights + keys_scored_per_step * index_key_bytes(c, itemsize)
        + keys_selected_per_step * kv_row_bytes(c, itemsize)
    )


def prefill_flops(c: dict, tokens: int, attended_pairs: float | None = None) -> float:
    """FLOPs of one prompt of ``tokens``, two per multiply-add: the
    projections (attention, indexer, router), the routed experts at
    ``experts_per_tok * held / n_experts`` pairs a token, the index scores
    of every causal pair, the attention over ``attended_pairs`` (query,
    key) pairs a layer (default: every causal pair, which is what a tiled
    kernel under a mask computes; the selected pairs alone are
    ``selected_pairs``), the head at the last position.  Softmax, norms and
    activations are left out."""
    causal = tokens * (tokens + 1) / 2
    pairs = causal if attended_pairs is None else attended_pairs
    per_token = 2 * (
        attention_params(c) + indexer_params(c) + router_params(c)
        + c["experts_per_tok"] * held(c) / c["n_experts"] * expert_params(c)
    )
    index = 2 * c["index_heads"] * c["index_dim"] * causal
    scores = 2 * 2 * c["n_heads"] * c["head_dim"] * pairs
    return float(
        c["n_layers"] * (tokens * per_token + index + scores)
        + 2 * c["hidden"] * c["vocab_size"]
    )


def selected_pairs(c: dict, tokens: int) -> float:
    """sum over a prompt's queries of min(topk, t + 1)."""
    k = c["index_topk"]
    if tokens <= k:
        return tokens * (tokens + 1) / 2
    return k * (k + 1) / 2 + (tokens - k) * k
