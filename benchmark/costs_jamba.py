"""What a step of the Jamba model needs, computed from shapes: the parameters
by part, a slot's state, the bytes a decode step must move given the slots
that were live and the K/V rows their positions say it attends, the bytes a
prompt's recurrence must move, the FLOPs of a prompt.  The algorithm's
counts, not the compiler's.  ``c`` is a configuration's ``graph.parameters``
(``models/jamba.py::Config``), which states every size."""

from __future__ import annotations

STATE_BYTES = 4  # the state's stated precision: float32


def itemsize(c: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[c["dtype"]]


def attn_layers(c: dict) -> int:
    """Layers ``l`` with ``l % period == offset`` (the family's convention)."""
    return sum(
        1 for l in range(c["n_layers"])
        if l % c["attn_layer_period"] == c["attn_layer_offset"]
    )


def ssm_layers(c: dict) -> int:
    return c["n_layers"] - attn_layers(c)


def d_inner(c: dict) -> int:
    return c["mamba_expand"] * c["hidden"]


def ssm_mixer_params(c: dict) -> int:
    """``Win``, the convolution and its bias, ``Wx``, the three inner norms,
    ``Wdt`` and ``b_dt``, ``A_log``, ``Dskip``, ``Wout``."""
    e, di = c["hidden"], d_inner(c)
    n, r, k = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    return (
        e * 2 * di + k * di + (di if c["mamba_conv_bias"] else 0)
        + di * (r + 2 * n) + (r + 2 * n) + r * di + di + di * n + di + di * e
    )


def attention_params(c: dict) -> int:
    """``Wq``, ``Wk``, ``Wv``, ``Wo``: no bias."""
    e, h, kv = c["hidden"], c["n_heads"], c["n_kv_heads"]
    d = e // h
    return e * h * d + 2 * e * kv * d + h * d * e


def mlp_params(c: dict) -> int:
    return 3 * c["hidden"] * c["ffn"]


def ssm_layer_params(c: dict) -> int:
    """A state-space layer with its MLP and two norms."""
    return ssm_mixer_params(c) + mlp_params(c) + 2 * c["hidden"]


def attn_layer_params(c: dict) -> int:
    return attention_params(c) + mlp_params(c) + 2 * c["hidden"]


def embedding_params(c: dict) -> int:
    """The embedding, which is the head too (tied)."""
    return c["vocab_size"] * c["hidden"]


def total_params(c: dict) -> int:
    return (
        ssm_layers(c) * ssm_layer_params(c) + attn_layers(c) * attn_layer_params(c)
        + embedding_params(c) + c["hidden"]
    )


def slot_state_bytes(c: dict) -> int:
    """One slot's state, whatever its context: every state-space layer's
    ``S (d_inner, d_state)`` float32 and its convolution tail ``(d_conv - 1,
    d_inner)`` in the served dtype."""
    di = d_inner(c)
    a_layer = (
        di * c["mamba_d_state"] * STATE_BYTES
        + (c["mamba_d_conv"] - 1) * di * itemsize(c)
    )
    return ssm_layers(c) * a_layer


def kv_row_bytes(c: dict) -> int:
    """K and V of one token on ONE attention layer."""
    return 2 * c["n_kv_heads"] * (c["hidden"] // c["n_heads"]) * itemsize(c)


def decode_weight_bytes(c: dict) -> int:
    """What every decode step reads whatever the batch: every layer's
    weights once and the head (the tied embedding, whole) once."""
    return total_params(c) * itemsize(c)


def decode_state_bytes(c: dict, slot_steps: float) -> float:
    """The state a step moves: a live slot's is read and written."""
    return 2 * slot_state_bytes(c) * slot_steps


def decode_step_bytes(c: dict, slot_steps: float, rows_live: float) -> float:
    """The bytes one decode step HAS to move: the weights, every live
    slot's state in and out (``slot_steps``: live slots in the step), and
    K and V of every row the live slots' positions say the attention layers
    attend (``rows_live``: summed over the attention layers)."""
    return (
        decode_weight_bytes(c) + decode_state_bytes(c, slot_steps)
        + kv_row_bytes(c) * rows_live
    )


def scan_token_bytes(c: dict) -> int:
    """What the prompt's recurrence moves for one token of one layer: ``c``
    in and ``y`` out in the served dtype, ``D_t`` in float32, ``B`` and ``C``
    in float32."""
    return d_inner(c) * (2 * itemsize(c) + 4) + 2 * c["mamba_d_state"] * 4


def scan_prompt_bytes(c: dict) -> int:
    """Once a prompt and layer: ``A`` in, the state out (float32), ``Dskip``."""
    di = d_inner(c)
    return 2 * di * c["mamba_d_state"] * 4 + di * 4


def scan_bytes(c: dict, real_tokens: float, prompts: float = 1.0) -> float:
    """The bytes the recurrence of ``prompts`` prompts of ``real_tokens``
    REAL tokens in all has to move, every state-space layer counted."""
    return ssm_layers(c) * (
        scan_token_bytes(c) * real_tokens + scan_prompt_bytes(c) * prompts
    )


def prefill_flops(c: dict, tokens: float) -> float:
    """Matrix FLOPs of a prompt of ``tokens`` (two a multiply-add): every
    layer's projections and MLP, the attention layers' causal pairs; the
    head runs on the last token alone."""
    e, h = c["hidden"], c["n_heads"]
    di, n, r = d_inner(c), c["mamba_d_state"], c["mamba_dt_rank"]
    ssm = e * 2 * di + di * (r + 2 * n) + r * di + di * e
    per_token = (
        ssm_layers(c) * (ssm + mlp_params(c))
        + attn_layers(c) * (attention_params(c) + mlp_params(c))
    )
    pairs = tokens * (tokens + 1) / 2
    return (
        2 * per_token * tokens + attn_layers(c) * 2 * 2 * pairs * h * (e // h)
        + 2 * embedding_params(c)
    )
