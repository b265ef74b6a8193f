"""The reference child: a process of its own, started after the engine has
exited (so the chip is free), that makes the served weights again from the
seed and holds the engine's probe outputs against the plain reference.

    python benchmark/reference/check.py --config benchmark/configs/X.json --seed N < probes.json

It reads ONE line of JSON from its standard input (the probes: what the
engine was asked and what it answered) and prints one line of JSON with
what it found.  It judges nothing: the margins are the configuration's,
applied by the caller through the judge that ``found["judge"]`` names.

This file is the child's frame and holds no kind's code and no table of
kinds.  ``config["reference"]["kind"]`` names a module ``kinds/<kind>.py``
beside it that gives ``check(config, graph, seed, chips, probes) -> dict``
and ``JUDGE``, the name of the judge (``judges/<judge>.py``) that holds its
findings to the configuration's limits unless ``config["reference"]
["judge"]`` names another.  A later PR adds a kind as files and edits none.
What a kind needs of the frame it imports from ``frame.py``: the laps, the
served dtype, a model that one device cannot hold spread over the
configuration's ``chips`` along the layer axis.  A kind runs on whatever
JAX serves on here: the chip on a chip run (float32 at ``highest`` matmul
precision), the CPU in a rehearsal.

A kind makes the weights by the program's own init call with the same key
(``PRNGKey(seed)``), cast the way the engine casts them: they ARE the
system under test's weights, and the only way to have them here; every line
of the forward pass is the benchmark's own, in a file beside this one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    import frame

    from seldon_core_tpu.utils.device import configure_compile_cache

    configure_compile_cache()  # the reference's programs are cached too
    with open(args.config) as f:
        config = json.load(f)
    graph = config["graph"]["parameters"]
    reference = config["reference"]
    kind = frame.named_module("kinds", reference["kind"])
    probes = json.loads(sys.stdin.readline())
    found = kind.check(config, graph, args.seed, int(config.get("chips", 1)), probes)
    # the judge the caller applies: the configuration's, or its kind's own
    found["judge"] = reference.get("judge") or kind.JUDGE
    found["child_seconds"] = {k: round(v, 2) for k, v in frame.CLOCK.items()}
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
