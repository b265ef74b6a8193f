"""Subprocess worker for the multi-host DCN mesh test.

Each invocation is one "TPU host": 4 virtual CPU devices, joining a
2-process mesh through ``parallel.maybe_initialize`` exactly as an engine
pod would (env contract from operator/resources.py).  The computation
shards a matmul over a (dp=2, tp=4) mesh spanning both processes, so XLA
must insert cross-process collectives; each process checks the global
result against numpy.

Run by tests/test_distributed.py — not a test module itself.
"""

import os
import sys


def main() -> None:
    ordinal = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # the operator's StatefulSet env contract (operator/resources.py)
    os.environ["SCT_NUM_PROCESSES"] = "2"
    os.environ["SCT_MESH_SERVICE"] = "dep-p1-mesh"
    os.environ["SCT_COORDINATOR_PORT"] = port
    os.environ["SCT_POD_NAME"] = f"dep-p1-engine-{ordinal}"
    # tests run on one machine: resolve the coordinator pod DNS to localhost
    os.environ["SCT_COORDINATOR_ADDRESS"] = f"localhost:{port}"
    os.environ["SCT_PROCESS_ID"] = str(ordinal)

    import jax

    from seldon_core_tpu.parallel import MeshPlan, make_mesh, maybe_initialize

    cfg = maybe_initialize()
    assert cfg is not None and cfg.num_processes == 2
    assert cfg.process_id == ordinal
    assert (ordinal == 0) == cfg.is_coordinator

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 8, "mesh must span both processes"
    assert jax.process_count() == 2

    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    rng = np.random.default_rng(0)
    x_np = rng.normal(size=(8, 16)).astype(np.float32)
    w_np = rng.normal(size=(16, 32)).astype(np.float32)

    x = jax.make_array_from_callback(
        x_np.shape,
        NamedSharding(mesh, P("dp", None)),
        lambda idx: x_np[idx],
    )
    w = jax.make_array_from_callback(
        w_np.shape,
        NamedSharding(mesh, P(None, "tp")),
        lambda idx: w_np[idx],
    )

    @jax.jit
    def step(x, w):
        return jax.nn.relu(x @ w).sum()

    # the scalar output is fully replicated: every process sees the global
    # value, proving the collectives crossed the process boundary
    out = float(step(x, w))
    expected = float(np.maximum(x_np @ w_np, 0.0).sum())
    assert abs(out - expected) < 1e-2 * max(1.0, abs(expected)), (out, expected)
    print(f"OK process={ordinal} out={out:.3f}")

    # --- full serving path: CompiledModel + MultihostDriver lead/follow ---
    # Both processes build the identical model over the shared mesh (exactly
    # what two engine pods do from the same graph spec); the coordinator
    # serves warmup + a request, the worker follows broadcast steps.
    from seldon_core_tpu.executor.compiled import BucketSpec, CompiledModel
    from seldon_core_tpu.executor.multihost import MultihostDriver

    driver = MultihostDriver(is_coordinator=cfg.is_coordinator, heartbeat_s=2.0)
    model = CompiledModel(
        lambda p, b: jax.nn.relu(b @ p["w"]),
        {"w": w_np},
        mesh=mesh,
        buckets=BucketSpec((4, 8)),
        name="mh",
        driver=driver,
    )
    # --- multi-host generative: the slot-cache decode loop across hosts ---
    # Both processes construct the identical model (tp=2 shards the KV
    # heads across the process boundary); the coordinator admits + decodes
    # through the driver, the worker follows.
    from seldon_core_tpu.executor.generation import GenerativeModel
    from seldon_core_tpu.models import llama
    from seldon_core_tpu.models.registry import get_family

    lcfg = llama.Config.tiny(max_seq=64)
    lparams = llama.init_params(jax.random.PRNGKey(0), lcfg)
    gen_mesh = make_mesh(MeshPlan(dp=4, tp=2))
    gmodel = GenerativeModel(
        lcfg,
        lparams,
        family_mod=llama,
        n_slots=2,
        mesh=gen_mesh,
        param_axes=get_family("llama").param_logical_axes(lparams),
        decode_block=4,
        name="mhgen",
        driver=driver,
    )

    if cfg.is_coordinator:
        driver.start_heartbeat()
        assert model.warmup((16,)) == 2
        got = model(x_np[:5])  # odd size: pads up to bucket 8
        want = np.maximum(x_np[:5] @ w_np, 0.0)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

        # greedy reference: local dense forward loop on this process only
        prompt = np.array([5, 9, 2, 17, 3], np.int32)
        ref = list(prompt)
        for _ in range(5):
            import jax.numpy as jnp

            logits = llama.forward(
                lparams, jnp.asarray([ref], jnp.int32), lcfg, seq_impl="dense"
            )
            ref.append(int(np.asarray(logits)[0, -1].argmax()))
        expected = ref[len(prompt):]

        # warmup drives prefill-bucket compiles AND reset() through the
        # driver — a coordinator-only reset device_put used to wedge the
        # slice (review regression)
        assert gmodel.warmup() > 0
        first = gmodel.admit(0, prompt, 0.0, 0)
        toks_seq, act_seq = gmodel.step_k(
            np.array([first, 0], np.int32),
            np.array([True, False]),
            np.zeros(2, np.float32),
            0,
            np.array([-1, -1], np.int32),
            np.array([4, 0], np.int32),
            4,
        )
        got_toks = [first] + [int(toks_seq[i, 0]) for i in range(4) if act_seq[i, 0]]
        assert got_toks == expected, (got_toks, expected)
        driver.shutdown()
        print(f"OK-generative process={ordinal}")
        print(f"OK-serving process={ordinal}")
    else:
        driver.follower_loop()
        print(f"OK-generative process={ordinal}")
        print(f"OK-serving process={ordinal}")


if __name__ == "__main__":
    main()
