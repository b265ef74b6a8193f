"""What a step of the Kimi-K2 share needs, computed from shapes: the
parameters by part, the bytes a decode step must read given the experts its
tokens touched and the latent rows its read brought in, the FLOPs of a
prompt.  The algorithm's counts, not the compiler's.  ``c`` is a
configuration's ``graph.parameters`` (``models/kimi_k2.py::Config``)."""

from __future__ import annotations


def held(c: dict) -> int:
    """Routed experts this share holds (``experts_held = "first:count"``)."""
    text = str(c.get("experts_held") or "")
    return int(text.partition(":")[2]) if text else int(c["n_experts"])


def dense_layers(c: dict) -> int:
    return int(c.get("n_dense_layers", 1))


def expert_layers(c: dict) -> int:
    return int(c["n_layers"]) - dense_layers(c)


def attention_params(c: dict) -> int:
    """Both down-projections and their norms, ``Wqb``, ``Wkvb`` (``W_UK``
    and ``W_UV``), ``Wo``, and the layer's two RMSNorms."""
    h, nh = c["hidden"], c["n_heads"]
    ql, cl = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_dim"], c["qk_rope_dim"], c["v_head_dim"]
    return (
        h * ql + ql + ql * nh * (dn + dr) + h * (cl + dr) + cl
        + cl * nh * (dn + dv) + nh * dv * h + 2 * h
    )


def absorb_params(c: dict) -> int:
    """``W_UK`` and ``W_UV``: what a decode step's absorption reads."""
    return c["kv_lora_rank"] * c["n_heads"] * (c["qk_nope_dim"] + c["v_head_dim"])


def expert_params(c: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * c["hidden"] * c["ffn"]


def dense_mlp_params(c: dict) -> int:
    return 3 * c["hidden"] * c["ffn_dense"]


def router_params(c: dict) -> int:
    """The router and its selection bias."""
    return c["hidden"] * c["n_experts"] + c["n_experts"]


def expert_layer_params_unrouted(c: dict) -> int:
    """What every token's step reads of an expert layer whatever the
    routing: attention, the shared experts, the router, the norms."""
    return (
        attention_params(c) + c.get("n_shared_experts", 1) * expert_params(c)
        + router_params(c)
    )


def expert_layer_params(c: dict) -> int:
    """An expert layer as this share holds it."""
    return expert_layer_params_unrouted(c) + held(c) * expert_params(c)


def dense_layer_params(c: dict) -> int:
    """A leading dense layer: attention and the 18,432-wide SwiGLU."""
    return attention_params(c) + dense_mlp_params(c)


def head_params(c: dict) -> int:
    """The untied head's slice and the final norm (a step reads both whole)."""
    return c["vocab_size"] * c["hidden"] + c["hidden"]


def share_params(c: dict) -> int:
    """All parameters here: the layers, the embedding's rows, the head."""
    return (
        dense_layers(c) * dense_layer_params(c)
        + expert_layers(c) * expert_layer_params(c)
        + c["vocab_size"] * c["hidden"] + head_params(c)
    )


def latent_row_bytes(c: dict, itemsize: int = 2) -> int:
    """What a token leaves in the pool on one layer: ``c`` and ``kr``."""
    return (c["kv_lora_rank"] + c["qk_rope_dim"]) * itemsize


def pool_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return c["n_layers"] * latent_row_bytes(c, itemsize)


def pool_bytes(c: dict, itemsize: int = 2) -> int:
    return c["kv_blocks"] * c["kv_block_size"] * pool_bytes_per_token(c, itemsize)


def by_head_row_bytes(c: dict, itemsize: int = 2) -> int:
    """What K and V by head would cost a token a layer: the cache this
    architecture does not keep."""
    return c["n_heads"] * (
        c["qk_nope_dim"] + c["qk_rope_dim"] + c["v_head_dim"]
    ) * itemsize


def decode_weight_bytes(c: dict, experts_touched_per_step: float,
                        itemsize: int = 2) -> float:
    """The weights one decode step has to read whatever implements it: every
    layer's attention, the dense layers' MLP, the shared experts, routers
    and norms, the head's slice, and the held experts the step's tokens
    TOUCHED (summed over the expert layers, from the program's counter: not
    all that are held, so a program that skips untouched experts cannot read
    over 100 %)."""
    return itemsize * (
        dense_layers(c) * dense_layer_params(c)
        + expert_layers(c) * expert_layer_params_unrouted(c)
        + head_params(c) + experts_touched_per_step * expert_params(c)
    )


def latent_read_bytes(c: dict, rows_per_step: float, itemsize: int = 2) -> float:
    """1,152 B for every latent row a step HAS to read: a live slot at
    position p attends p + 1 rows a layer (summed over layers and slots:
    the program's ``mla.rows_live``, counted from the positions and not by
    the read, so a read that fetches a row twice cannot raise its share)."""
    return rows_per_step * latent_row_bytes(c, itemsize)


def decode_step_bytes(c: dict, experts_touched_per_step: float,
                      rows_per_step: float, itemsize: int = 2) -> float:
    """Bytes one decode step has to read from HBM.  Activations, what is
    written and the embedding rows gathered are left out: under 0.1 %."""
    return (
        decode_weight_bytes(c, experts_touched_per_step, itemsize)
        + latent_read_bytes(c, rows_per_step, itemsize)
    )


def prefill_flops(c: dict, tokens: float, pairs_held: float | None = None) -> float:
    """FLOPs of one prompt of ``tokens`` through this share, two per
    multiply-add: the projections (the up-projection ``c Wkvb`` among them),
    the scores (192 wide) and their product with V (128 wide) of every
    causal pair, the dense layers' MLP, the shared experts, the router, the
    routed experts at ``pairs_held`` (token, expert) pairs a token a layer
    (the expected ``experts_per_tok * held / n_experts`` unless a counter
    says); the head at the last position only.  Softmax, norms and
    activations are left out."""
    nh = c["n_heads"]
    causal = tokens * (tokens + 1) / 2
    scores = 2 * nh * (
        c["qk_nope_dim"] + c["qk_rope_dim"] + c["v_head_dim"]
    ) * causal
    pairs = (
        c["experts_per_tok"] * held(c) / c["n_experts"] if pairs_held is None
        else pairs_held
    )
    moe_token = 2 * (
        attention_params(c) + c.get("n_shared_experts", 1) * expert_params(c)
        + router_params(c) + pairs * expert_params(c)
    )
    dense_token = 2 * (attention_params(c) + dense_mlp_params(c))
    return float(
        tokens * (dense_layers(c) * dense_token + expert_layers(c) * moe_token)
        + c["n_layers"] * scores + 2 * c["hidden"] * c["vocab_size"]
    )
