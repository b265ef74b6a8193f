"""What the reference child's frame gives a reference kind: the laps of the
child's clock, the served dtype, and a model spread over the configuration's
``chips`` along its layer axis, one layer after the other where its weights
are.  A kind (``kinds/<kind>.py``) imports this; it copies none of it.
``check.py`` runs as ``__main__``, so what a kind shares with it lives here.
"""

from __future__ import annotations

import importlib.util
import operator
import os
import re
import time

HERE = os.path.dirname(os.path.abspath(__file__))
HOLDS = {"<=": operator.le, ">=": operator.ge, "==": operator.eq}
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")

CLOCK: dict[str, float] = {}
_T = [time.perf_counter()]


def lap(name: str) -> None:
    """Where the child's time went: kept beside what it found."""
    now = time.perf_counter()
    CLOCK[name] = CLOCK.get(name, 0.0) + now - _T[0]
    _T[0] = now


def named_module(folder: str, name: str):
    """The module ``<folder>/<name>.py``: how the harness finds a reference
    kind (``kinds``) and a judge (``judges``) beside this file, and a
    metric's reader.  No table: a later PR adds a file and names it in its
    configuration or in BENCHMARK.json."""
    folder = os.path.join(HERE, folder)
    path = os.path.join(folder, f"{name}.py")
    if not NAME.match(name) or not os.path.exists(path):
        raise LookupError(f"no {name}.py in {folder}")
    spec = importlib.util.spec_from_file_location(
        f"{os.path.basename(folder)}_" + re.sub(r"[.-]", "_", name), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def all_hold(rows: list[tuple]) -> bool:
    """Whether every row (number, found, "<=" | ">=" | "==", limit) holds:
    a judge judges by the rows it prints."""
    return all(HOLDS[op](value, limit) for _, value, op, limit in rows)


def served_dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def layer_devices(n_layers: int, chips: int) -> list:
    """The devices a stack of ``n_layers`` is spread over: the first
    ``chips``, or one where the layers do not divide among them."""
    import jax

    devices = jax.devices()[:chips]
    return devices if n_layers % len(devices) == 0 else devices[:1]


def init_spread(init, key, devices: list, stacked: str = "layers"):
    """``init(key)``'s tree, made under one jit (the key is an argument:
    one compiled program for every seed) with the entry ``stacked`` split
    over ``devices`` along its leading (layer) axis and every other entry
    on each of them."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("layers",))
    shardings = {
        k: jax.tree.map(
            lambda _: NamedSharding(mesh, P("layers") if k == stacked else P()), v
        )
        for k, v in jax.eval_shape(init, key).items()
    }
    params = jax.jit(init, out_shardings=shardings)(key)
    jax.block_until_ready(params)
    return params


def local_stacks(stack: dict, n_devices: int):
    """Each device's own part of a stacked entry, in layer order, as
    single-device arrays: what runs there runs where its weights are."""
    per_dev = {
        k: sorted(v.addressable_shards, key=lambda s: s.index[0].start or 0)
        for k, v in stack.items()
    }
    for d in range(n_devices):
        yield {k: shards[d].data for k, shards in per_dev.items()}
