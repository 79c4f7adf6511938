"""Mesh environment: logical-axis helpers shared by model code.

Model code never hard-codes mesh axis names; it asks the active ``MeshEnv``
for constraint specs. With no env set (CPU smoke tests) every helper is a
no-op, so the same model code runs on 1 device and on the 512-chip mesh.

Physical mesh (launch/mesh.py):
    single-pod  (data=16, model=16)            axes ("data", "model")
    multi-pod   (pod=2, data=16, model=16)     axes ("pod", "data", "model")

Logical mapping:
    batch / sequence-shards -> ("pod", "data")   ["dp"]
    heads / d_ff / experts  -> "model"           ["tp"]
    fsdp param dim          -> "data"            (replicated across pods;
                                                  grads all-reduce over pod)
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes by default, which put the
    sharding into array types: a sharded result then fails later
    unsharded indexing, and ``with_sharding_constraint`` refuses the
    axes.  Code here shards through specs and constraints, so it runs on
    ``Auto`` meshes whatever the caller built.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@dataclasses.dataclass(frozen=True)
class MeshEnv:
    mesh: Mesh | None = None
    dp: tuple[str, ...] = ()     # batch axes (pod, data)
    fsdp: str | None = None      # param-shard axis (data)
    tp: str | None = None        # tensor axis (model)

    @property
    def active(self) -> bool:
        return self.mesh is not None

    def dp_size(self) -> int:
        if not self.active:
            return 1
        import math
        return math.prod(self.mesh.shape[a] for a in self.dp)

    def tp_size(self) -> int:
        return self.mesh.shape[self.tp] if self.active and self.tp else 1


_local = threading.local()


def set_env(env: MeshEnv) -> None:
    _local.env = env


def get_env() -> MeshEnv:
    return getattr(_local, "env", MeshEnv())


def env_from_mesh(mesh: Mesh | None) -> MeshEnv:
    if mesh is None:
        return MeshEnv()
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    return MeshEnv(mesh=mesh,
                   dp=dp,
                   fsdp="data" if "data" in names else None,
                   tp="model" if "model" in names else None)


class use_mesh:
    """Context manager: activate a MeshEnv (and the mesh itself)."""

    def __init__(self, mesh: Mesh | None):
        self.env = env_from_mesh(mesh)
        self._prev: MeshEnv | None = None

    def __enter__(self):
        self._prev = get_env()
        set_env(self.env)
        return self.env

    def __exit__(self, *exc):
        set_env(self._prev or MeshEnv())
        return False


def shard(x: jax.Array, *spec: Any) -> jax.Array:
    """Apply a sharding constraint if a mesh env is active, else no-op.

    Spec entries use LOGICAL names: "dp" (batch axes), "tp" (model axis),
    "fsdp" (data axis), None, or tuples thereof.
    """
    env = get_env()
    if not env.active:
        return x
    phys = []
    for s in spec:
        phys.append(_resolve(env, s))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(env.mesh, P(*phys)))


def _resolve(env: MeshEnv, s):
    if s is None:
        return None
    if isinstance(s, tuple):
        out: list[str] = []
        for part in s:
            r = _resolve(env, part)
            if r is None:
                continue
            out.extend(r if isinstance(r, tuple) else (r,))
        return tuple(out) if out else None
    if s == "dp":
        return env.dp if env.dp else None
    if s == "tp":
        return env.tp
    if s == "fsdp":
        return env.fsdp
    return s  # literal mesh axis name


def logical_spec(*spec: Any) -> P:
    """Resolve a logical spec to a physical PartitionSpec for the active env
    (used for in_shardings/out_shardings at jit boundaries)."""
    env = get_env()
    if not env.active:
        return P()
    return P(*[_resolve(env, s) for s in spec])
