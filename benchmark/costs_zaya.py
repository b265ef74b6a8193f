"""What a step of the ZAYA1 model needs, computed from shapes: the parameters
by part, a slot's tails, the bytes a decode step must move given the experts
its tokens chose, the K/V rows the live slots' positions say it attends and
the slots that were live, and the FLOPs of a prompt.  The algorithm's counts,
not the compiler's.  ``c`` is a configuration's ``graph.parameters``
(``models/zaya.py::Config``), which states every size."""

from __future__ import annotations


def itemsize(c: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[c["dtype"]]


def latent(c: dict) -> int:
    """``u``'s width: the query heads' latent and the key heads'."""
    return (c["n_heads"] + c["n_kv_heads"]) * c["head_dim"]


def cca_params(c: dict) -> int:
    """``Wq``, ``Wk``, ``Wv1 | Wv2``, ``Wo``: no bias."""
    e, d = c["hidden"], c["head_dim"]
    return e * latent(c) + e * c["n_kv_heads"] * d + c["n_heads"] * d * e


def conv_params(c: dict) -> int:
    """The depthwise taps and bias, a head's matrix a tap and its bias."""
    d, g = c["head_dim"], c["n_heads"] + c["n_kv_heads"]
    return (c["cca_time0"] + 1) * latent(c) + g * (c["cca_time1"] * d * d + d)


def small_params(c: dict) -> int:
    """Both norms, ``tau``, and four residual vectors a sublayer."""
    return 2 * c["hidden"] + c["n_kv_heads"] + 8 * c["hidden"]


def router_params(c: dict) -> int:
    """``Wd`` and ``bd``, ``gam``, the norm, two hidden layers with biases,
    the 17 outputs (no bias) and the balancing biases."""
    e, r, n = c["hidden"], c["router_hidden_size"], c["n_experts"] + 1
    return e * r + 3 * r + 2 * (r * r + r) + r * n + n


def expert_params(c: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * c["hidden"] * c["moe_intermediate_size"]


def dense_block_params(c: dict) -> int:
    """What every step reads of a block whatever the routing."""
    return cca_params(c) + conv_params(c) + small_params(c) + router_params(c)


def block_params(c: dict) -> int:
    return dense_block_params(c) + c["n_experts"] * expert_params(c)


def embedding_params(c: dict) -> int:
    """The embedding, which is the head too (tied)."""
    return c["vocab_size"] * c["hidden"]


def total_params(c: dict) -> int:
    return c["n_layers"] * block_params(c) + embedding_params(c) + c["hidden"]


def slot_tail_bytes(c: dict) -> int:
    """One slot's tails, whatever its context: a block's ``u`` and ``c0`` of
    the taps before and ``h Wv2`` of the token before, in the served dtype."""
    values = (
        (c["cca_time0"] + c["cca_time1"] - 2) * latent(c)
        + c["n_kv_heads"] * c["head_dim"] // 2
    )
    return c["n_layers"] * values * itemsize(c)


def kv_row_bytes(c: dict) -> int:
    """K and V of one token on ONE block."""
    return 2 * c["n_kv_heads"] * c["head_dim"] * itemsize(c)


def decode_dense_bytes(c: dict) -> int:
    """What every decode step reads whatever the batch: every block's
    weights outside its experts, the final norm, and the head (the tied
    embedding, whole) once."""
    return (
        c["n_layers"] * dense_block_params(c) + embedding_params(c) + c["hidden"]
    ) * itemsize(c)


def decode_kv_bytes(c: dict, rows_live: float) -> float:
    """K and V of every row attended (``rows_live``: summed over blocks)."""
    return kv_row_bytes(c) * rows_live


def decode_step_bytes(c: dict, experts: float, rows_live: float,
                      slots_live: float) -> float:
    """The bytes one decode step HAS to move whatever implements it: the
    dense part and the head once, every expert some token chose
    (``experts``: summed over the blocks), K and V of every row the live
    slots' positions say the blocks attend (``rows_live``: summed over the
    blocks), and for every live slot its new K/V row written and its tails
    in and out, every block."""
    per_slot = c["n_layers"] * kv_row_bytes(c) + 2 * slot_tail_bytes(c)
    return (
        decode_dense_bytes(c) + experts * expert_params(c) * itemsize(c)
        + decode_kv_bytes(c, rows_live) + slots_live * per_slot
    )


def counted(c: dict, d: dict) -> tuple[float, ...] | None:
    """``(steps, experts touched, experts read, rows live, slots live)`` a
    step, from the program's counters over a window (``d``: their delta);
    None where no decode step was counted.  Top-1: a live slot is one
    (token, choice) pair a block."""
    steps = d.get("zaya.steps", 0)
    if steps <= 0 or "attn.rows_live" not in d or "moe.pairs_routed" not in d:
        return None
    return (
        float(steps), d["moe.experts_touched"] / steps, d["moe.experts_read"] / steps,
        d["attn.rows_live"] / steps, d["moe.pairs_routed"] / (c["n_layers"] * steps),
    )


def prefill_flops(c: dict, tokens: float) -> float:
    """Matrix FLOPs of a prompt of ``tokens`` (two a multiply-add): every
    block's projections, per-head convolution, router and ONE expert a token
    (top-1; a token that chose the no-op does less: the counter has the true
    count), the causal pairs' scores and values; the head on the last token
    alone."""
    d = c["head_dim"]
    g = c["n_heads"] + c["n_kv_heads"]
    r = c["router_hidden_size"]
    per_token = (
        cca_params(c) + g * c["cca_time1"] * d * d
        + c["hidden"] * r + 2 * r * r + r * (c["n_experts"] + 1)
        + expert_params(c)
    )
    pairs = tokens * (tokens + 1) / 2
    return (
        c["n_layers"] * (2 * per_token * tokens + 2 * 2 * pairs * c["n_heads"] * d)
        + 2 * embedding_params(c)
    )
