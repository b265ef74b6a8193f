"""Logical-axis sharding rules.

Params and activations are annotated with *logical* axis names
(``"batch"``, ``"hidden"``, ``"heads"``, ``"seq"``, ...); a
:class:`ShardingRules` table maps them onto mesh axes.  This is the
scaling-book recipe: pick a mesh, annotate shardings, let XLA insert the
collectives.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = str | tuple[str, ...] | None


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or None = replicated)."""

    rules: tuple[tuple[str, MeshAxis], ...] = (
        ("batch", ("dp", "fsdp")),
        ("seq", "sp"),
        ("heads", "tp"),
        ("kv_heads", "tp"),
        ("hidden", None),
        ("mlp", "tp"),
        ("vocab", "tp"),
        ("embed", None),
        ("expert", "tp"),
        ("conv_out", None),
        ("head_dim", None),
    )

    def mesh_axis(self, logical: str | None) -> MeshAxis:
        if logical is None:
            return None
        for name, axis in self.rules:
            if name == logical:
                return axis
        return None

    def spec(self, logical_axes: tuple[str | None, ...]) -> P:
        return P(*(self.mesh_axis(a) for a in logical_axes))

    def with_overrides(self, **overrides: MeshAxis) -> "ShardingRules":
        out = [(n, overrides.get(n, a)) for n, a in self.rules]
        for n, a in overrides.items():
            if n not in dict(self.rules):
                out.append((n, a))
        return ShardingRules(tuple(out))


DEFAULT_RULES = ShardingRules()

# FSDP-style serving of models too big for one chip's HBM: shard params along
# fsdp too, all-gathered per layer by XLA.
FSDP_RULES = DEFAULT_RULES.with_overrides(hidden="fsdp", embed="fsdp")


def logical_sharding(
    mesh: Mesh, logical_axes: tuple[str | None, ...], rules: ShardingRules = DEFAULT_RULES
) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(logical_axes))


def param_shardings(params, mesh: Mesh, annotations, rules: ShardingRules = DEFAULT_RULES):
    """The ``NamedSharding`` of every leaf of ``params`` (arrays or shape
    structs): ``annotations`` is a matching pytree of logical-axis tuples
    (or ``None`` for replicated)."""

    def _sharding(_, ann):
        if ann is None:
            return NamedSharding(mesh, P())
        return logical_sharding(mesh, ann, rules)

    return jax.tree.map(
        _sharding, params, annotations, is_leaf=lambda x: x is None
    )


def shard_params(params, mesh: Mesh, annotations, rules: ShardingRules = DEFAULT_RULES):
    """Place a param pytree on the mesh (device_put, zero host copy beyond
    the first transfer); see :func:`param_shardings` for ``annotations``."""
    return jax.tree.map(
        jax.device_put, params, param_shardings(params, mesh, annotations, rules)
    )
