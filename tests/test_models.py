"""Model-zoo tests: shapes, probability outputs, sharded parity, KV-cache
decode consistency, generation, training step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import bert, cnn, llama, mlp, registry, resnet
from seldon_core_tpu.parallel import best_mesh

RNG = jax.random.PRNGKey(0)


class TestSmallModels:
    def test_mlp_probabilities(self):
        cfg = mlp.Config(in_features=16, hidden=32, n_classes=3)
        params = mlp.init_params(RNG, cfg)
        out = mlp.apply(params, np.ones((4, 16), np.float32), cfg)
        assert out.shape == (4, 3)
        np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)

    def test_cnn_accepts_flat_and_image(self):
        cfg = cnn.Config(image_size=8, hidden=16)
        params = cnn.init_params(RNG, cfg)
        flat = cnn.apply(params, np.ones((2, 64), np.float32), cfg)
        img = cnn.apply(params, np.ones((2, 8, 8, 1), np.float32), cfg)
        assert flat.shape == img.shape == (2, 10)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(img), rtol=1e-5)

    def test_resnet_tiny_forward(self):
        cfg = resnet.Config(stage_sizes=(1, 1), width=8, n_classes=5, image_size=16)
        params = resnet.init_params(RNG, cfg)
        out = resnet.apply(params, np.ones((2, 16, 16, 3), np.float32), cfg)
        assert out.shape == (2, 5)
        np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)

    def test_bert_tiny_forward(self):
        cfg = bert.Config(vocab_size=64, hidden=16, n_layers=2, n_heads=2, ffn=32, max_len=32)
        params = bert.init_params(RNG, cfg)
        ids = np.array([[2, 5, 9, 0, 0], [3, 4, 0, 0, 0]], np.int32)
        out = bert.apply(params, ids, cfg)
        assert out.shape == (2, 2)
        np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)

    def test_bert_padding_invariance(self):
        """Extra padding tokens must not change the [CLS] prediction."""
        cfg = bert.Config(vocab_size=64, hidden=16, n_layers=1, n_heads=2, ffn=32, max_len=32)
        params = bert.init_params(RNG, cfg)
        a = bert.apply(params, np.array([[2, 5, 9]], np.int32), cfg)
        b = bert.apply(params, np.array([[2, 5, 9, 0, 0, 0]], np.int32), cfg)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


class TestLlama:
    cfg = llama.Config.tiny(max_seq=32)

    def test_forward_shapes(self):
        params = llama.init_params(RNG, self.cfg)
        toks = np.ones((2, 8), np.int32)
        logits = llama.forward(params, jnp.asarray(toks), self.cfg)
        assert logits.shape == (2, 8, self.cfg.vocab_size)

    def test_decode_matches_forward(self):
        """Prefill + decode steps must reproduce full-sequence logits."""
        params = llama.init_params(RNG, self.cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, self.cfg.vocab_size)
        full = llama.forward(params, toks, self.cfg)

        cache = llama.init_cache(self.cfg, 1)
        logits, cache = llama.prefill(params, toks[:, :3], self.cfg, cache)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, 2]), atol=1e-4)
        for i in range(3, 6):
            logits, cache = llama.decode_step(params, toks[:, i], cache, self.cfg)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[:, i]), atol=1e-4,
                err_msg=f"step {i}",
            )

    def test_generate_greedy_deterministic(self):
        params = llama.init_params(RNG, self.cfg)
        toks = np.ones((2, 4), np.int32)
        a = llama.generate(params, jnp.asarray(toks), self.cfg, max_new_tokens=5)
        b = llama.generate(params, jnp.asarray(toks), self.cfg, max_new_tokens=5)
        assert a.shape == (2, 5)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ring_prefill_matches_dense(self):
        """Sequence-parallel scoring path == dense path."""
        mesh = best_mesh(8, tp=1, sp=8)
        params = llama.init_params(RNG, self.cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, self.cfg.vocab_size)
        dense = llama.forward(params, toks, self.cfg, seq_impl="dense")
        ring = llama.forward(params, toks, self.cfg, mesh=mesh, seq_impl="ring")
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=2e-4)

    def test_train_step_reduces_loss(self):
        params = llama.init_params(RNG, self.cfg)
        optimizer, train_step = llama.make_train_step(self.cfg)
        opt_state = optimizer.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, self.cfg.vocab_size)
        step = jax.jit(train_step)
        _, _, loss0 = step(params, opt_state, toks)
        p, o = params, opt_state
        for _ in range(5):
            p, o, loss = step(p, o, toks)
        assert float(loss) < float(loss0)


class TestRegistry:
    @pytest.mark.parametrize("family", ["mlp", "cnn", "resnet", "bert", "llama"])
    def test_build_and_run_tiny(self, family):
        m = registry.build_compiled(family, preset="tiny")
        cfg = registry.resolve_config(family, "tiny")
        x = registry.example_input(family, cfg, batch=2)
        out = m(x)
        assert out.shape[0] == 2

    def test_build_sharded_bert(self):
        mesh = best_mesh(8, tp=2)
        m = registry.build_compiled("bert", preset="tiny", mesh=mesh)
        cfg = registry.resolve_config("bert", "tiny")
        x = registry.example_input("bert", cfg, batch=8)
        out = m(x)
        assert out.shape == (8, cfg.n_classes)
        # attention projections really sharded over tp
        q = m.params["params"]["layer_0"]["attention"]["query"]["kernel"]
        assert "tp" in tuple(q.sharding.spec)

    def test_unknown_family_raises(self):
        with pytest.raises(KeyError):
            registry.get_family("nope")

    def test_config_overrides(self):
        cfg = registry.resolve_config("mlp", "tiny", n_classes=7)
        assert cfg.n_classes == 7 and dataclasses.is_dataclass(cfg)


class TestUint8Ingest:
    """The binary image-serving path: uint8 pixels in, normalization fused
    into the jitted forward (models/resnet.py::apply)."""

    def test_uint8_matches_prenormalized_float(self):
        from seldon_core_tpu.executor import BucketSpec

        m = registry.build_compiled(
            "resnet", preset="tiny", buckets=BucketSpec((4,))
        )
        img = np.random.default_rng(0).integers(
            0, 256, size=(4, 32, 32, 3), dtype=np.uint8
        )
        norm = (img.astype(np.float32) / 255.0 - resnet.IMAGENET_MEAN) / np.asarray(
            resnet.IMAGENET_STD
        )
        out8 = np.asarray(m(img), np.float32)
        outf = np.asarray(m(norm.astype(np.float32)), np.float32)
        np.testing.assert_allclose(out8, outf, atol=1e-5)

    def test_input_dtype_warms_uint8_bucket(self):
        comp = registry.build_component(
            "resnet", preset="tiny", input_dtype="uint8", max_batch=2
        )
        assert comp.warmup_example.dtype == np.uint8


    def test_seq_warms_token_models_at_the_served_length(self):
        comp = registry.build_component("bert", preset="tiny", seq=48)
        assert comp.warmup_example.shape == (1, 48)
        assert comp.warmup_example.dtype == np.int32
        with pytest.raises(TypeError, match="token models"):
            registry.build_component("mlp", preset="tiny", seq=48)


class TestRoofline:
    def test_peaks_are_keyed_by_exact_device_kind(self):
        """``"TPU v5 lite"`` is the v5e (Cloud TPU v5e documentation); an
        unknown TPU is an error, never a neighbour's figures or a silent
        None; off-TPU there is no peak to compare against."""
        import types

        from seldon_core_tpu.utils import roofline

        def dev(platform, kind):
            return types.SimpleNamespace(platform=platform, device_kind=kind)

        v5e = dev("tpu", "TPU v5 lite")
        assert roofline.chip_peak_flops(v5e) == 197e12
        assert roofline.chip_hbm_bandwidth(v5e) == 819e9
        for kind in ("TPU v5", "TPU v5p", "TPU v9 lite"):
            with pytest.raises(ValueError, match="no published peaks"):
                roofline.chip_peak_flops(dev("tpu", kind))
            with pytest.raises(ValueError, match="no published peaks"):
                roofline.chip_hbm_bandwidth(dev("tpu", kind))
        assert roofline.chip_peak_flops(dev("cpu", "cpu")) is None
        assert roofline.chip_hbm_bandwidth() is None  # the CPU test backend


@pytest.mark.parametrize("family", sorted(registry.GENERATIVE_FAMILIES))
def test_a_family_imports_layers_and_no_sibling(family):
    """A family's module builds from the neutral modules (``models/layers.py``,
    ``paged.py``, ``moe.py``) and imports from no other family of
    ``GENERATIVE_FAMILIES``: an edit to one family's file then changes no
    other family's programs."""
    import ast

    mod = registry.GENERATIVE_FAMILIES[family]
    assert mod.__name__ == f"seldon_core_tpu.models.{family}"
    siblings = set(registry.GENERATIVE_FAMILIES) - {family}
    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            imported.add(base)
            imported |= {f"{base}.{a.name}" for a in node.names}
    reached = {part for name in imported for part in name.split(".")}
    assert not reached & siblings, sorted(reached & siblings)
