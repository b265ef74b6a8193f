"""What the chip bring-up fixed, held on the CPU (the chip's own check is
``chip_smoke.py``): one process per chip, a compile cache that can be
placed from outside, no fallback that hides the device, and a warmup that
compiles what serving then runs."""

import base64
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _predictor_env(implementation: str, **params) -> str:
    graph = {
        "name": "m", "type": "MODEL", "implementation": implementation,
        "parameters": [
            {"name": k, "value": v, "type": "STRING"} for k, v in params.items()
        ],
    }
    return base64.b64encode(
        json.dumps({"name": "p", "graph": graph}).encode()
    ).decode()


class TestCompileCachePlacement:
    """``utils/device.py::configure_compile_cache`` — the directory is part
    of what a hit depends on, so it comes from ``JAX_COMPILATION_CACHE_DIR``
    or is one fixed path in the checkout, never anything per-process."""

    PROBE = (
        "import json, jax\n"
        "from seldon_core_tpu.utils.device import configure_compile_cache\n"
        "got = configure_compile_cache()\n"
        "print(json.dumps({'returned': got,\n"
        "  'dir': jax.config.jax_compilation_cache_dir,\n"
        "  'min_secs': jax.config.jax_persistent_cache_min_compile_time_secs,\n"
        "  'min_bytes': jax.config.jax_persistent_cache_min_entry_size_bytes}))\n"
    )

    def _probe(self, **env) -> dict:
        """The helper in a fresh process (it never initialises a backend,
        so a platform this sandbox lacks is fine to name)."""
        clean = {
            k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
        }
        out = subprocess.run(
            [sys.executable, "-c", self.PROBE], cwd=REPO,
            env={**clean, "PYTHONPATH": REPO, **env},
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    # sct: test-hygiene-ok one ~2 s `python -c` probe per call, no server
    def test_env_dir_is_left_alone(self, tmp_path):
        got = self._probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        # JAX read the variable itself; the helper set no other directory
        assert got["returned"] == got["dir"] == str(tmp_path)

    # sct: test-hygiene-ok one ~2 s `python -c` probe per call, no server
    def test_default_is_one_fixed_path_in_the_checkout(self):
        first, second = self._probe(), self._probe(JAX_PLATFORMS="tpu")
        want = os.path.join(REPO, ".jax_cache")
        assert first["returned"] == second["returned"] == want
        assert first["dir"] == second["dir"] == want
        # sub-second programs (small buckets) must be written too
        assert first["min_secs"] == 0.0 and first["min_bytes"] == -1

    # sct: test-hygiene-ok one ~2 s `python -c` probe per call, no server
    def test_cpu_pinned_process_keeps_no_cache(self):
        got = self._probe(JAX_PLATFORMS="cpu")
        assert got["returned"] is None and got["dir"] is None


class TestOneProcessPerChip:
    def test_workers_with_a_device_unit_is_refused(self, monkeypatch, capsys):
        from seldon_core_tpu.engine import app

        monkeypatch.setenv(
            "ENGINE_PREDICTOR",
            _predictor_env("JAX_MODEL", family="mlp", preset="tiny"),
        )
        with pytest.raises(SystemExit) as exc:
            app.main(["--workers", "2"])
        assert exc.value.code == 2
        err = capsys.readouterr().err
        assert "--workers 2 with device units ['m']" in err
        assert "one process" in err

    def test_device_units_are_found_anywhere_in_the_graph(self):
        from seldon_core_tpu.engine.app import jax_units
        from seldon_core_tpu.graph.spec import PredictorSpec

        leaf = {"type": "MODEL", "implementation": "SIMPLE_MODEL"}
        spec = PredictorSpec.model_validate({"name": "p", "graph": {
            "name": "r", "type": "ROUTER", "implementation": "RANDOM_ABTEST",
            "children": [
                {"name": "a", **leaf},
                {"name": "g", "type": "MODEL",
                 "implementation": "JAX_GENERATIVE"},
            ],
        }})
        assert jax_units(spec.graph) == ["g"]
        assert jax_units(spec.graph.children[0]) == []


class TestKernelsCompileOffTheCpu:
    """Pallas interpret mode is the CPU's, never "anything not called
    tpu": a chip whose backend carries another name still gets Mosaic."""

    @pytest.mark.parametrize(
        "backend,interpret", [("cpu", True), ("tpu", False), ("other", False)]
    )
    def test_interpret_default(self, monkeypatch, backend, interpret):
        from jax.experimental import pallas as pl

        from seldon_core_tpu.ops import flash_attention, paged_decode_attention

        seen = []

        class Captured(Exception):
            pass

        def fake_pallas_call(*a, interpret, **kw):
            seen.append(interpret)
            raise Captured

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
        x = jnp.zeros((1, 2, 16, 8), jnp.float32)
        with pytest.raises(Captured):
            flash_attention.__wrapped__(x, x, x)  # under the jit wrapper
        with pytest.raises(Captured):
            paged_decode_attention(
                jnp.zeros((1, 1, 2, 8)), jnp.zeros((3, 4, 2, 8)),
                jnp.zeros((3, 4, 2, 8)), jnp.zeros((1, 2), jnp.int32),
                jnp.zeros((1,), jnp.int32),
            )
        assert seen == [interpret, interpret]


class TestWarmupCompilesWhatServingRuns:
    """jit keys a program on how its arguments are placed, not only on
    their shapes.  Warmup used to run every program on an uncommitted
    cache and host carry vectors; serving then ran them on a committed
    cache and on the device carry — so each warmed program compiled a
    second time on its first request, after ``/ready``, where the model's
    own compile counters could not see it."""

    @pytest.mark.parametrize("extra", [{}, {"kv_cache_dtype": "int8"}])
    def test_no_xla_compile_after_warmup(self, extra):
        from seldon_core_tpu.executor.generation import GenerativeModel
        from seldon_core_tpu.models import llama
        from seldon_core_tpu.utils.device import xla_compile_count

        cfg = llama.Config.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=16, **extra
        )
        xla_compile_count()
        model.warmup()
        warmed = xla_compile_count()
        assert warmed > 0
        tok = model.admit(
            0, np.array([5, 9, 2, 17, 3], np.int32), 0.0, 0, reserve_tokens=60
        )
        cur, active = np.zeros(4, np.int32), np.zeros(4, bool)
        cur[0], active[0] = tok, True
        first = model.step_k_dispatch(
            cur, active, np.zeros(4, np.float32), 0,
            np.full(4, -1, np.int32), np.full(4, 60, np.int32), 16,
        )
        # the overlapped pipeline's continue feeds the on-device carry
        second = model.step_k_continue(active, 1, 16)
        for handle in (first, second):
            toks, emitted = model.step_k_fetch(handle)
            assert emitted[:, 0].all()
        assert xla_compile_count() == warmed
