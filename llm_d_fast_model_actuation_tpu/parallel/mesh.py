"""Device meshes and named-axis sharding rules.

The scaling recipe: pick a mesh, annotate shardings with logical axis names,
let XLA insert the collectives, profile, iterate. Mesh axes:

  ``dp``  data parallel (batch)          — all-reduce of grads / independent requests
  ``pp``  pipeline parallel (layers)     — lax.scan over stages + ppermute
  ``tp``  tensor parallel (heads/mlp)    — all-gather/reduce-scatter on ICI
  ``sp``  sequence/context parallel      — ring attention over the seq axis
  ``ep``  expert parallel (MoE experts)  — all_to_all token routing

Axis order is outer-to-inner by communication intensity: tp (and sp) innermost
so their collectives ride ICI within a host; dp/pp outermost so they can span
DCN between slices.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "pp", "sp", "tp", "ep")


@dataclass(frozen=True)
class MeshPlan:
    """Degrees of each parallelism axis. Product must equal device count."""

    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep

    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.dp, self.pp, self.sp, self.tp, self.ep)

    def describe(self) -> str:
        return ",".join(
            f"{n}={v}" for n, v in zip(AXES, self.axis_sizes()) if v > 1
        ) or "single-device"


def make_mesh(
    plan: MeshPlan, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build a Mesh for `plan` over `devices` (default: all local devices).

    Uses `jax.experimental.mesh_utils` device ordering on real TPU slices so
    that the innermost axes (tp/sp) land on ICI-adjacent chips.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if plan.size != n:
        raise ValueError(f"mesh plan {plan} needs {plan.size} devices, have {n}")
    shape = plan.axis_sizes()
    if devices[0].platform == "tpu":
        # a topology mesh_utils cannot lay out raises: flat order on a real
        # slice would silently put tp neighbours on non-adjacent chips
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    else:
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(dev_array, AXES)


# Logical axis name -> mesh axes. Tensors are annotated with logical names;
# these rules translate to PartitionSpecs. Mirrors the flax "logical axis
# rules" idiom so model code never hard-codes mesh axes.
LOGICAL_RULES: Dict[str, Any] = {
    "batch": "dp",
    "seq": "sp",  # sequence/context parallel shards the sequence axis
    "embed": None,  # replicated over tp (activations)
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "mlp": "tp",
    "vocab": "tp",
    "layers": "pp",
    "expert": "ep",
    "kv_batch": "dp",  # KV-cache page axis follows data parallel
    None: None,
}


def logical_axis_rules(overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    rules = dict(LOGICAL_RULES)
    if overrides:
        rules.update(overrides)
    return rules


def spec_for(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Dict[str, Any]] = None,
) -> P:
    """Logical axis names -> PartitionSpec via the rules table."""
    rules = rules or LOGICAL_RULES
    return P(*(rules.get(ax) for ax in logical_axes))


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Dict[str, Any]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules))


def logical_shardings(mesh: Mesh, axes_tree: Any, rules=None) -> Any:
    """Pytree of NamedShardings on `mesh` for a pytree of logical axis
    tuples (None leaf = fully replicated)."""
    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes or (), rules),
        axes_tree,
        is_leaf=lambda x: x is None or isinstance(x, tuple),
    )


def shard_pytree(tree: Any, mesh: Mesh, axes_tree: Any, rules=None) -> Any:
    """`jax.device_put` a pytree onto `mesh` per a matching pytree of logical
    axis tuples (None leaf = fully replicated)."""
    return jax.device_put(tree, logical_shardings(mesh, axes_tree, rules))


def serving_mesh(tp: int, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The engine's tp-mesh over the first ``tp`` local devices — the ONE
    construction every serving-path site uses (runtime build, AOT warmup,
    digest qualification), so Mesh equality (devices + axis names) holds
    across all of them and NamedShardings captured at sleep compare equal
    to the ones a later build produces. A host with more visible devices
    than ``tp`` serves from the leading ones (the launcher pins visible
    chips per instance; the 8-virtual-device CPU test runner relies on
    the slice too)."""
    if devices is None:
        devices = jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"tensor_parallel_size {tp} needs {tp} devices, have "
            f"{len(devices)}"
        )
    return make_mesh(MeshPlan(tp=tp), list(devices)[:tp])


def flat_spec_strs(axes_tree: Any, rules=None) -> Dict[str, str]:
    """Flat '/'-joined weight key -> ``str(PartitionSpec)`` over a
    logical-axes pytree (models.registry.logical_axes_for). This is the
    shard-view input of the mesh-qualified content digests
    (engine/chunk_store.py:qualify_digest): derived from the MODEL
    CONFIG, not from placed arrays, so the host-only prefetch staging
    path and the placed runtime build qualify identically."""
    out: Dict[str, str] = {}

    def walk(node: Any, prefix: Tuple[str, ...]) -> None:
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, prefix + (k,))
        else:
            spec = spec_for(node, rules) if node is not None else P()
            out["/".join(prefix)] = str(spec)

    walk(axes_tree, ())
    return out


def plan_for_devices(
    n: int, tp: Optional[int] = None, sp: int = 1, pp: int = 1, ep: int = 1
) -> MeshPlan:
    """Choose a plan for `n` devices: given tp (default min(n, 8) capped to a
    divisor of n), the rest goes to dp."""
    if tp is None:
        tp = 1
        for cand in (8, 4, 2, 1):
            if cand <= n and n % cand == 0:
                tp = cand
                break
    inner = tp * sp * pp * ep
    if n % inner != 0:
        raise ValueError(f"{n} devices not divisible by tp*sp*pp*ep={inner}")
    return MeshPlan(dp=n // inner, pp=pp, sp=sp, tp=tp, ep=ep)


def host_local_mesh(plan: MeshPlan) -> Mesh:
    return make_mesh(plan)


def replicate(tree: Any, mesh: Mesh) -> Any:
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def mesh_plan_fields() -> Tuple[str, ...]:
    return tuple(f.name for f in dataclasses.fields(MeshPlan))
