"""Logical-axis sharding: one place that maps model-level axis names onto
physical mesh axes.

Models annotate tensors with *logical* axes ("batch", "heads", "ffn", ...).
The table below maps those onto whatever physical mesh is active.  The same
model code therefore runs on a single CPU device (no mesh -> no-op), the
single-pod 16x16 mesh, and the multi-pod 2x16x16 mesh.

Design notes
------------
* ``batch`` maps to ("pod", "data"): data parallelism spans pods so only
  gradient/metric all-reduces cross the slow DCN links.
* ``heads``/``kv_heads``/``ffn``/``experts``/``vocab`` map to "model"
  (tensor/expert parallelism stays inside a pod on fast ICI).
* A mesh may lack some axes (e.g. no "pod" on the single-pod mesh); unknown
  axes are silently dropped from the spec, which is exactly the semantics we
  want for elastic meshes.
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> physical mesh axis (or tuple of axes)
LOGICAL_RULES: dict[str, Union[str, tuple[str, ...], None]] = {
    "batch": ("pod", "data"),
    "expert_batch": ("pod", "data"),   # token dim inside MoE dispatch
    "seq": None,                        # sequence kept unsharded by default
    "seq_sp": "data",                   # sequence-parallel variant (opt-in)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "experts": "model",
    "expert_ffn": None,
    "vocab": "model",
    "kv_lora": None,
    # decode KV-cache sequence dim: sharded over "model" for MQA/low-kv-head
    # archs (split-K decode: each model shard scores a context slice, XLA
    # combines the softmax with small all-reduces).  Only consulted when the
    # kv-head dim cannot shard (see attention.cache_specs).
    "kv_seq": "model",
    "state": None,
    "conv": None,
    "layers": None,
    "frames": None,
    "patches": None,
    "opt_state": ("data",),             # extra ZeRO-1 axis for optimizer moments
    "fsdp": ("data",),                  # FSDP/ZeRO-3 parameter axis
    # simulator axes (repro.core.simjax): the chunked scan shard_maps its
    # per-tick step over a 1-D "functions" mesh (per-function state and
    # histograms device-local, one psum at chunk boundaries), and the
    # frontier batches grid points over a 1-D "points" mesh
    "functions": "functions",
    "points": "points",
}

_state = threading.local()


def set_mesh(mesh: Optional[Mesh]) -> None:
    """Install *mesh* as the ambient mesh used by :func:`shard`."""
    _state.mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


class use_mesh:
    """Context manager installing an ambient mesh."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh
        self._prev: Optional[Mesh] = None

    def __enter__(self):
        self._prev = current_mesh()
        set_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_mesh(self._prev)
        return False


def _resolve(axis: Optional[str], mesh_axes: Sequence[str]):
    """Map one logical axis name to mesh axes present on the current mesh.

    Tuple rules stay tuples even when only one physical axis survives
    (e.g. ``("pod", "data")`` on a pod-less mesh resolves to ``("data",)``,
    not ``"data"``): PartitionSpec treats the two forms as distinct entries,
    and collapsing would make a spec's shape depend on which mesh is active.
    String rules resolve to the bare axis name.
    """
    if axis is None:
        return None
    rule = LOGICAL_RULES.get(axis, None)
    if rule is None:
        return None
    if isinstance(rule, str):
        return rule if rule in mesh_axes else None
    present = tuple(a for a in rule if a in mesh_axes)
    return present or None


def logical_to_spec(logical: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> P:
    """Translate a tuple of logical axis names into a PartitionSpec."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return P()
    mesh_axes = tuple(mesh.axis_names)
    return P(*[_resolve(a, mesh_axes) for a in logical])


def sharding_for(logical: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(logical, mesh))


def device_mesh(devices: int, axis: str) -> Mesh:
    """1-D mesh over the first ``devices`` local devices, named ``axis``.

    The simulator's sharded dispatch uses this for its "functions" /
    "points" meshes; on CPU hosts pair it with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    avail = jax.devices()
    if devices < 1:
        raise ValueError(f"device_mesh needs >= 1 device, got {devices}")
    if devices > len(avail):
        raise ValueError(f"device_mesh({devices}, {axis!r}): "
                         f"{too_few_devices(devices, avail)}")
    return Mesh(np.asarray(avail[:devices]), (axis,))


def too_few_devices(wanted: int, avail) -> str:
    """Explain a device request the host cannot meet; only the CPU backend
    can be given more (virtual) devices, through ``XLA_FLAGS``."""
    msg = f"only {len(avail)} local device(s) visible"
    if avail and avail[0].platform == "cpu":
        msg += (f" — on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={wanted}")
    return msg


def sanitize_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide evenly (e.g. batch=1 decode,
    odd vocab sizes): sharding degrades gracefully instead of erroring."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if size and shape[i] % size == 0 else None)
    return P(*out)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint expressed in logical axes (no-op w/o mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = sanitize_spec(logical_to_spec(logical, mesh), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_axis_size(logical_axis: str, mesh: Optional[Mesh] = None) -> int:
    """Product of physical mesh axis sizes a logical axis maps onto (1 if unmapped)."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return 1
    rule = LOGICAL_RULES.get(logical_axis)
    if rule is None:
        return 1
    if isinstance(rule, str):
        rule = (rule,)
    size = 1
    for a in rule:
        if a in mesh.axis_names:
            size *= mesh.shape[a]
    return size


def div_axis(logical_axis: Optional[str], dim_size: int) -> Optional[str]:
    """Use *logical_axis* only if dim_size divides evenly on the current mesh."""
    if logical_axis is None:
        return None
    n = mesh_axis_size(logical_axis)
    if n <= 1 or dim_size % n != 0:
        return None
    return logical_axis


def spec_tree_to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    """Convert a pytree of logical-axis tuples into NamedShardings."""

    def conv(leaf):
        if leaf is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, logical_to_spec(leaf, mesh))

    return jax.tree.map(conv, spec_tree, is_leaf=lambda l: l is None or isinstance(l, tuple))


def fsdp_specs(spec_tree: Any, shape_tree: Any, min_dim: int = 1024) -> Any:
    """ZeRO-3/FSDP: additionally shard each large weight over the data axis
    on its first free, evenly-divisible dimension.  GSPMD then all-gathers
    the shard inside the (scanned) layer and reduce-scatters its gradient —
    the standard FSDP collective schedule, for free.
    """
    n = mesh_axis_size("fsdp")
    is_spec = lambda l: l is None or isinstance(l, tuple)

    def free(ax):  # dim is free if its logical axis maps to no mesh axis
        return ax is None or mesh_axis_size(ax) <= 1

    def f(spec, sd):
        shape = sd.shape
        if spec is None:
            spec = (None,) * len(shape)
        if n <= 1 or len(shape) < 2:
            return spec
        out = list(spec)
        for i, (ax, dim) in enumerate(zip(spec, shape)):
            if free(ax) and dim >= min_dim and dim % n == 0:
                out[i] = "fsdp"
                break
        return tuple(out)

    return jax.tree.map(f, spec_tree, shape_tree, is_leaf=is_spec)
