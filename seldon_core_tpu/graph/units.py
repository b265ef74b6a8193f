"""Component contract and built-in graph units.

A *component* is the user-supplied (or built-in) object behind a graph node.
The contract is duck-typed exactly like the reference wrapper runtime
(reference: wrappers/python/model_microservice.py:23-33,
router_microservice.py:18-22, transformer_microservice.py:15-38):

    predict(X, feature_names) -> ndarray          MODEL
    route(X, feature_names) -> int                ROUTER
    aggregate(Xs, features_list) -> ndarray       COMBINER
    transform_input(X, feature_names) -> ndarray  TRANSFORMER
    transform_output(X, feature_names) -> ndarray OUTPUT_TRANSFORMER
    send_feedback(X, feature_names, reward, truth, routing)  optional
    class_names: list[str]                        optional

Any method may be ``async def``.  Components may also implement the ``*_raw``
variants taking/returning :class:`Payload` for full control of meta/encoding.

Built-ins double as test fixtures and benchmark stubs, the reference's own
pattern (engine/.../predictors/SimpleModelUnit.java:33-46 et al.).
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from seldon_core_tpu.graph.spec import Implementation


class GraphUnitError(Exception):
    """A unit rejected its input (maps to Status FAILURE on the wire)."""


class SeldonComponent:
    """Optional convenience base class; duck typing is what matters."""

    def init_metadata(self) -> dict[str, Any]:
        return {}

    def tags(self) -> dict[str, Any]:
        return {}

    def metrics(self) -> list[dict[str, Any]]:
        return []


# ---------------------------------------------------------------------------
# Built-in units
# ---------------------------------------------------------------------------

class SimpleModel(SeldonComponent):
    """Stub model returning a constant 3-class score row per input row —
    the reference's benchmark/default model
    (reference: engine/.../predictors/SimpleModelUnit.java:33-46)."""

    INLINE_SYNC = True  # microseconds of python math; skip the executor hop
    # DETERMINISTIC marks a component whose output is a pure function of
    # its input — the caching plane (docs/CACHING.md) only ever serves a
    # MODEL node from the response cache when the component declares it.
    # Stateful (Mahalanobis), randomized (RandomABTest), and feedback-
    # driven (bandit routers) components must NOT carry the mark.
    DETERMINISTIC = True

    values = np.array([0.1, 0.9, 0.5])
    class_names = ["class0", "class1", "class2"]

    def predict(self, X: np.ndarray, names: list[str]) -> np.ndarray:
        rows = X.shape[0] if getattr(X, "ndim", 0) >= 2 else 1
        return np.tile(self.values, (rows, 1))


class SimpleRouter(SeldonComponent):
    """Always routes to child 0
    (reference: engine/.../predictors/SimpleRouterUnit.java:28-31)."""

    INLINE_SYNC = True  # microseconds of python math; skip the executor hop
    DETERMINISTIC = True  # always child 0

    def route(self, X: np.ndarray, names: list[str]) -> int:
        return 0


class RandomABTest(SeldonComponent):
    """Routes to child 0 with probability ``ratioA``, else child 1; seeded for
    reproducibility (reference: engine/.../predictors/RandomABTestUnit.java:33-57,
    seeded Random(1337))."""

    INLINE_SYNC = True  # microseconds of python math; skip the executor hop

    def __init__(self, ratioA: float = 0.5, seed: int = 1337, **_: Any):
        self.ratio_a = float(ratioA)
        self._rng = np.random.default_rng(seed)

    def route(self, X: np.ndarray, names: list[str]) -> int:
        return 0 if self._rng.random() < self.ratio_a else 1


class AverageCombiner(SeldonComponent):
    """Element-wise mean of children outputs with strict shape agreement
    (reference: engine/.../predictors/AverageCombinerUnit.java:34-81).

    NOT inline-sync: the stack+mean copies scale with arbitrary child
    payload sizes — milliseconds of numpy on big batches belongs on the
    thread pool, not the event loop."""

    DETERMINISTIC = True  # pure element-wise mean

    def aggregate(self, Xs: list[np.ndarray], features: list[list[str]]) -> np.ndarray:
        if not Xs:
            raise GraphUnitError("AverageCombiner needs at least one input")
        arrs = [np.asarray(x, dtype=np.float64) for x in Xs]
        shape = arrs[0].shape
        for i, a in enumerate(arrs[1:], start=1):
            if a.shape != shape:
                raise GraphUnitError(
                    f"AverageCombiner shape mismatch: input 0 {shape} vs input {i} {a.shape}"
                )
        return np.mean(np.stack(arrs), axis=0)


class EpsilonGreedy(SeldonComponent):
    """Multi-armed-bandit router: explore with probability epsilon, otherwise
    exploit the best-performing branch; rewards arrive via the feedback loop
    (reference behaviour: examples/routers/epsilon_greedy/EpsilonGreedy.py:12-60)."""

    INLINE_SYNC = True  # microseconds of python math; skip the executor hop

    def __init__(
        self,
        n_branches: int = 2,
        epsilon: float = 0.1,
        verbose: bool = False,
        seed: int | None = 1337,
        **_: Any,
    ):
        if n_branches < 1:
            raise GraphUnitError("n_branches must be >= 1")
        self.n_branches = int(n_branches)
        self.epsilon = float(epsilon)
        self.verbose = bool(verbose)
        self._rng = np.random.default_rng(seed)
        self.pulls = np.zeros(self.n_branches, dtype=np.int64)
        self.value = np.zeros(self.n_branches, dtype=np.float64)

    def route(self, X: np.ndarray, names: list[str]) -> int:
        if self._rng.random() < self.epsilon:
            return int(self._rng.integers(self.n_branches))
        return int(np.argmax(self.value))

    def send_feedback(
        self,
        X: np.ndarray,
        names: list[str],
        reward: float,
        truth: Any = None,
        routing: int | None = None,
    ) -> None:
        if routing is None or not (0 <= routing < self.n_branches):
            return
        self.pulls[routing] += 1
        n = self.pulls[routing]
        # incremental mean of observed rewards per branch
        self.value[routing] += (reward - self.value[routing]) / n


class ThompsonSampling(SeldonComponent):
    """Beta-Bernoulli Thompson-sampling router (TPU-native extra beyond the
    reference's bandit example): sample a win-rate per branch, route argmax."""

    INLINE_SYNC = True  # microseconds of python math; skip the executor hop

    def __init__(self, n_branches: int = 2, seed: int | None = 1337, **_: Any):
        self.n_branches = int(n_branches)
        self._rng = np.random.default_rng(seed)
        self.alpha = np.ones(self.n_branches)
        self.beta = np.ones(self.n_branches)

    def route(self, X: np.ndarray, names: list[str]) -> int:
        samples = self._rng.beta(self.alpha, self.beta)
        return int(np.argmax(samples))

    def send_feedback(self, X, names, reward, truth=None, routing=None) -> None:
        if routing is None or not (0 <= routing < self.n_branches):
            return
        if reward > 0:
            self.alpha[routing] += reward
        else:
            self.beta[routing] += 1.0


class MahalanobisOutlier(SeldonComponent):
    """Online Mahalanobis-distance outlier scorer: incremental mean/covariance
    over the request stream, score = squared Mahalanobis distance of each row;
    annotates ``meta.tags.outlier_score`` as a TRANSFORMER
    (reference behaviour: examples/transformers/outlier_mahalanobis/
    OutlierMahalanobis.py:6-80 and wrappers/python/
    outlier_detector_microservice.py:23-56)."""

    def __init__(self, n_components: int = 0, n_stdev: float = 3.0, **_: Any):
        self.n_components = int(n_components)
        self.n_stdev = float(n_stdev)
        self.count = 0
        self._mean: np.ndarray | None = None
        self._m2: np.ndarray | None = None  # sum of outer-product deviations
        self._last_scores: np.ndarray | None = None

    def score(self, X: np.ndarray) -> np.ndarray:
        X = np.atleast_2d(np.asarray(X, dtype=np.float64))
        d = X.shape[1]
        if self._mean is None:
            self._mean = np.zeros(d)
            self._m2 = np.zeros((d, d))
        scores = np.zeros(X.shape[0])
        for i, row in enumerate(X):
            if self.count >= 2:
                cov = self._m2 / (self.count - 1)
                cov = cov + 1e-6 * np.eye(d)  # ridge for invertibility
                delta = row - self._mean
                scores[i] = float(delta @ np.linalg.solve(cov, delta))
            # Welford update
            self.count += 1
            delta = row - self._mean
            self._mean += delta / self.count
            self._m2 += np.outer(delta, row - self._mean)
        self._last_scores = scores
        return scores

    def transform_input(self, X: np.ndarray, names: list[str]) -> np.ndarray:
        self.score(X)
        return X

    def tags(self) -> dict[str, Any]:
        if self._last_scores is None:
            return {}
        return {"outlier_score": self._last_scores.tolist()}


# ---------------------------------------------------------------------------
# Implementation registry
# ---------------------------------------------------------------------------

_BUILTINS: dict[Implementation, Callable[..., Any]] = {
    Implementation.SIMPLE_MODEL: SimpleModel,
    Implementation.SIMPLE_ROUTER: SimpleRouter,
    Implementation.RANDOM_ABTEST: RandomABTest,
    Implementation.AVERAGE_COMBINER: AverageCombiner,
    Implementation.EPSILON_GREEDY: EpsilonGreedy,
    Implementation.THOMPSON_SAMPLING: ThompsonSampling,
    Implementation.MAHALANOBIS_OUTLIER: MahalanobisOutlier,
    Implementation.JAX_MODEL: lambda **p: _jax_model(p),
    Implementation.JAX_GENERATIVE: lambda **p: _jax_generative(p),
    # LLM graph plane (docs/GRAPHS.md) — lazy imports: the graphllm
    # package pulls runtime settings the plain graph path never needs
    Implementation.CASCADE_ROUTER: lambda **p: _graphllm("CascadeRouter", p),
    Implementation.GUARDRAIL: lambda **p: _graphllm("Guardrail", p),
}


def _graphllm(cls_name: str, parameters: dict[str, Any]) -> Any:
    import seldon_core_tpu.graphllm as graphllm

    return getattr(graphllm, cls_name)(**parameters)


def _parse_dtype(raw: Any, impl_name: str) -> Any:
    """Map a graph-parameter dtype string to a JAX dtype (None = keep)."""
    import jax.numpy as jnp

    dtypes = {"bfloat16": jnp.bfloat16, "float16": jnp.float16, "float32": None, None: None}
    if raw not in dtypes:
        raise GraphUnitError(
            f"{impl_name} dtype must be one of "
            f"{sorted(k for k in dtypes if k)}, got {raw!r}"
        )
    return dtypes[raw]


def _parse_mesh(raw: Any, impl_name: str):
    """Graph-parameter mesh request -> jax.sharding.Mesh.

    ``"auto"`` picks a serving mesh over every visible device (all hosts of
    the slice — the mesh spans processes on multi-host, and CompiledModel
    coordinates steps through the MultihostDriver); ``"tp=4,fsdp=2"`` etc.
    names an explicit MeshPlan factorization.
    """
    if raw is None:
        return None
    from seldon_core_tpu.parallel import MeshPlan, best_mesh, make_mesh

    raw = str(raw).strip()
    if raw in ("auto", "all"):
        return best_mesh()
    try:
        axes = {}
        for part in raw.split(","):
            k, _, v = part.partition("=")
            axes[k.strip()] = int(v)
        return make_mesh(MeshPlan(**axes))
    except (ValueError, TypeError) as e:
        raise GraphUnitError(
            f"{impl_name} mesh must be 'auto' or 'dp=..,fsdp=..,tp=..,sp=..', "
            f"got {raw!r}: {e}"
        ) from None


def _jax_model(parameters: dict[str, Any]) -> Any:
    """JAX_MODEL implementation: compile a model-zoo family on device.

    Graph parameters: ``family`` (required), ``preset``, ``dtype``
    ("bfloat16"/"float16"/"float32"), ``max_batch``, ``max_delay_ms``,
    ``buckets`` (comma-separated batch ladder, e.g. "8,32" — big models
    want few compiled programs), ``mesh`` ("auto" or "tp=4,fsdp=2" — shards
    params over the slice per the family's logical axes), ``input_dtype``
    (warm the buckets for a non-default wire dtype, e.g. "uint8" images
    normalized on device), ``seq`` (token models: warm the buckets at the
    sequence length requests arrive at — a program is compiled per length,
    and the default example's is a placeholder), plus any model-config
    field override (e.g. ``n_classes``).
    """
    from seldon_core_tpu.models import registry as model_registry

    params = dict(parameters)
    try:
        family = params.pop("family")
    except KeyError:
        raise GraphUnitError("JAX_MODEL requires a 'family' parameter") from None
    dtype = _parse_dtype(params.pop("dtype", None), "JAX_MODEL")
    mesh = _parse_mesh(params.pop("mesh", None), "JAX_MODEL")
    if mesh is not None:
        params["mesh"] = mesh
    sharding = str(params.pop("sharding", "default")).strip()
    if sharding == "fsdp":
        from seldon_core_tpu.parallel.sharding import FSDP_RULES

        params["rules"] = FSDP_RULES
    elif sharding != "default":
        raise GraphUnitError(
            f"JAX_MODEL sharding must be 'default' or 'fsdp', got {sharding!r}"
        )
    raw_buckets = params.pop("buckets", None)
    if raw_buckets is not None:
        from seldon_core_tpu.executor import BucketSpec

        try:
            sizes = tuple(sorted(int(s) for s in str(raw_buckets).split(",")))
            if not sizes or any(s < 1 for s in sizes):
                raise ValueError(sizes)
        except ValueError:
            raise GraphUnitError(
                f"buckets must be comma-separated positive ints, got {raw_buckets!r}"
            ) from None
        params["buckets"] = BucketSpec(sizes)
    try:
        return model_registry.build_component(family, dtype=dtype, **params)
    except (KeyError, TypeError) as e:
        raise GraphUnitError(str(e)) from e


def _jax_generative(parameters: dict[str, Any]) -> Any:
    """JAX_GENERATIVE implementation: continuous-batching token generation.

    Graph parameters: ``family`` (default "llama"), ``preset``, ``n_slots``,
    ``max_new_tokens``, ``temperature``, ``top_k`` (fused on-device top-k
    sampling), ``eos_id``, ``dtype``, ``checkpoint``, ``seq_impl``,
    ``decode_block``, ``overlap`` (overlapped decode pipeline,
    docs/PERFORMANCE.md), ``kv_prefix_reuse``, ``prefix_dram_gb``
    (host-DRAM prefix tier, docs/CACHING.md), ``spec_draft`` /
    ``spec_ngram`` / ``spec_hist`` (fused self-speculative decoding) with
    ``spec_method`` / ``spec_heads`` / ``spec_heads_path`` /
    ``spec_draft_model`` (learned proposers: fused Medusa-style heads or a
    co-resident draft model, docs/PERFORMANCE.md §6),
    ``kv_cache_dtype`` (``int8`` paged-KV quantization), ``prefill_chunk``
    (Sarathi-style chunked prefill interleaved with decode),
    ``decode_kernel`` (fused Pallas paged decode-attention kernel),
    ``lora_rank`` / ``lora_slots`` / ``lora_targets`` / ``lora_adapters``
    / ``adapter`` (batched multi-LoRA serving, docs/MULTITENANT.md),
    ``pack_class`` / ``pack_slo_ms`` (chip packing: this deployment's QoS
    class and queue-wait SLO band on a time-shared device,
    docs/PACKING.md), ``conf_signal`` (compile the cascade confidence
    signal into the fused decode programs) and ``embed`` (warm the
    pooled-embedding programs for the /embeddings route — docs/GRAPHS.md),
    plus model-config overrides.
    """
    from seldon_core_tpu.models import registry as model_registry

    params = dict(parameters)
    family = params.pop("family", "llama")
    dtype = _parse_dtype(params.pop("dtype", None), "JAX_GENERATIVE")
    mesh = _parse_mesh(params.pop("mesh", None), "JAX_GENERATIVE")
    if mesh is not None:
        params["mesh"] = mesh
    try:
        return model_registry.build_generative_component(
            family, dtype=dtype, **params
        )
    except (KeyError, TypeError) as e:
        raise GraphUnitError(str(e)) from e


def create_builtin(impl: Implementation, parameters: dict[str, Any]) -> Any:
    """Instantiate a built-in implementation with its typed parameters
    (reference analogue: PredictorConfigBean's implementation->bean map)."""
    try:
        factory = _BUILTINS[impl]
    except KeyError:
        raise GraphUnitError(f"no built-in implementation {impl!r}") from None
    return factory(**parameters)


def has_builtin(impl: Implementation) -> bool:
    return impl in _BUILTINS
