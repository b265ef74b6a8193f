"""The system under test as a child process: ``python -m
seldon_core_tpu.engine.app`` serving one graph, driven over HTTP.  This
process never imports jax: the child owns the chip."""

from __future__ import annotations

import base64
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def graph_of(config: dict, seed: int) -> dict:
    """The engine graph of a configuration file, weights from ``seed``."""
    types = {bool: "BOOL", int: "INT", float: "FLOAT", str: "STRING"}
    params = {**config["graph"]["parameters"], "rng": int(seed)}
    return {
        "name": "unit", "type": "MODEL",
        "implementation": config["graph"]["implementation"],
        "parameters": [
            {"name": k, "type": types[type(v)],
             "value": json.dumps(v) if isinstance(v, bool) else str(v)}
            for k, v in params.items()
        ],
    }


class Engine:
    def __init__(self, graph: dict, platform: str, log_path: str, extra_env: dict):
        self.port = free_port()
        self.log_path = log_path
        self.log = open(log_path, "wb")
        env = {**os.environ, **extra_env, "JAX_PLATFORMS": platform}
        # the program places its compile cache at <checkout>/.jax_cache
        # unless this is set; either way a fixed directory inside (or given
        # to) this checkout, so only a checkout's first run compiles
        env["ENGINE_PREDICTOR"] = base64.b64encode(
            json.dumps({"name": "bench", "graph": graph}).encode()
        ).decode()
        env["ENGINE_GRPC_OPTIONAL"] = "1"
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.engine.app",
             "--port", str(self.port), "--grpc-port", str(free_port())],
            env=env, cwd=ROOT, stdout=self.log, stderr=subprocess.STDOUT,
        )

    @property
    def base(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def get_json(self, path: str, timeout: float = 30.0) -> dict:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return json.loads(r.read())

    def wait_ready(self, timeout: float) -> None:
        t0 = time.monotonic()
        while True:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"engine exited rc={self.proc.returncode} before /ready"
                )
            try:
                with urllib.request.urlopen(self.base + "/ready", timeout=5) as r:
                    if r.status == 200:
                        return
            except urllib.error.HTTPError as e:
                text = e.read().decode(errors="replace")
                if text.startswith("warmup failed"):
                    raise BenchFailure(f"engine /ready says: {text[:500]}")
            except OSError:
                pass
            if time.monotonic() - t0 > timeout:
                raise BenchFailure(f"engine not ready within {timeout:.0f}s")
            time.sleep(0.25)

    def warmup(self) -> dict:
        warm = self.get_json("/stats/warmup")["warmup"]
        if not warm["warmed"] or warm["error"] is not None:
            raise BenchFailure(f"engine warmup: {warm['error']}")
        if warm["device"] is None:
            raise BenchFailure("/stats/warmup reports no device")
        return warm

    def stop(self) -> None:
        """SIGTERM, and wait until the child has gone: only then is the chip
        free for the next process."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()

    def log_tail(self, limit: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(max(0, os.path.getsize(self.log_path) - limit))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"(no engine log: {e})"
