"""The plain references against the program's own forward passes, at tiny
widths on the CPU, float32: two independent writings of one architecture
have to agree to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np


def test_llama_decoder_matches_models_llama_apply():
    from seldon_core_tpu.models import llama

    import llama_decoder

    cfg = llama.Config.tiny()
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, size=24)
    lg = llama_decoder.logits(
        params, tokens.tolist(), n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
    )
    assert lg.shape == (24, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = llama.apply(params, jnp.asarray(tokens)[None], cfg)[0]
        full = llama.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    np.testing.assert_allclose(jax.nn.softmax(lg[-1]), want, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(lg, full, rtol=2e-4, atol=2e-4)


def test_bert_encoder_matches_models_bert_apply():
    from seldon_core_tpu.models import bert

    import bert_encoder

    cfg = bert.Config(vocab_size=128, hidden=32, n_layers=2, n_heads=2, ffn=64, max_len=64)
    params = bert.init_params(jax.random.PRNGKey(1), cfg)
    ids = np.random.default_rng(0).integers(1, 128, size=(4, 16)).astype(np.int32)
    ids[1, 10:] = 0  # padding masks keys
    got = bert_encoder.probabilities(params, ids, n_layers=2)
    with jax.default_matmul_precision("highest"):
        want = bert.apply(params, jnp.asarray(ids), cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
