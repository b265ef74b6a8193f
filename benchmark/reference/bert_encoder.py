"""Plain reference for the BERT encoder classifier (arXiv:1810.04805): token
+ position + segment embeddings, layer norm, N post-norm blocks of
multi-head self-attention and a GELU feed-forward, tanh pooler on the first
token, softmax classifier.  Straightforward ``jax.numpy``, float32, highest
matmul precision.

``params`` is the served flax tree (``params/{tok_emb,pos_emb,seg_emb,
ln_emb,layer_i/...,pooler,head}``); each tensor is raised to float32 where
used.  Departures from the paper, both the served model's: GELU in its tanh
approximation (flax's default), padding is token id 0 and masks keys only.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _ln(x, p, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def probabilities(params, token_ids, *, n_layers, pad_id=0):
    """Class probabilities ``(B, n_classes)`` for a ``(B, L)`` token batch."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params["params"])
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        mask = ids != pad_id
        x = (
            p["tok_emb"]["embedding"][ids]
            + p["pos_emb"]["embedding"][jnp.arange(ids.shape[1])][None]
            + p["seg_emb"]["embedding"][0][None, None]
        )
        x = _ln(x, p["ln_emb"])
        for i in range(n_layers):
            lp = p[f"layer_{i}"]
            at = lp["attention"]
            proj = lambda n: (  # noqa: E731
                jnp.einsum("ble,ehd->blhd", x, at[n]["kernel"]) + at[n]["bias"]
            )
            q, k, v = proj("query"), proj("key"), proj("value")
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
            s = jnp.where(mask[:, None, None, :], s, -1e30)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            a = jnp.einsum("bqhd,hde->bqe", o, at["out"]["kernel"]) + at["out"]["bias"]
            x = _ln(x + a, lp["ln_att"])
            h = _gelu_tanh(x @ lp["ffn_up"]["kernel"] + lp["ffn_up"]["bias"])
            h = h @ lp["ffn_down"]["kernel"] + lp["ffn_down"]["bias"]
            x = _ln(x + h, lp["ln_ffn"])
        pooled = jnp.tanh(x[:, 0] @ p["pooler"]["kernel"] + p["pooler"]["bias"])
        return jax.nn.softmax(pooled @ p["head"]["kernel"] + p["head"]["bias"], -1)
