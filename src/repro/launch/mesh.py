"""Production mesh construction + sharding-rule binding.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: ``(16, 16) = ("data", "model")`` — 256
chips.  Multi-pod: ``(2, 16, 16) = ("pod", "data", "model")`` — 512 chips;
``pod`` is a second data-parallel axis (inter-pod gradient all-reduce over
DCI, intra-pod reduce-scatter over ICI).

Logical-axis bindings (see models/sharding.py):

* ``batch`` → ("pod", "data")   activations' batch dim
* ``model`` → "model"           tensor parallel
* ``fsdp``  → ("pod", "data")   ZeRO-3 parameter/optimizer sharding: every
  ≥2-D parameter shards one eligible dim across the DP axes; XLA SPMD
  inserts the per-layer all-gather (forward) and reduce-scatter (backward)
  — without this the 480B configs cannot fit 16 GB/chip (DESIGN.md §5)
* ``seq``   → "data"            sequence sharding for batch-1 long decode

`long_500k` (global_batch=1) rebinds ``batch → None`` and shards the
KV-cache/sequence dim over ``data`` instead.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.models import sharding as shd


def auto_mesh(shape, axes, devices) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The solver and the model code shard by ``NamedSharding`` /
    ``shard_map`` specs and let the compiler propagate the rest, which
    needs Auto axes; ``jax.make_mesh`` now defaults to Explicit ones.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def make_solve_mesh(n_devices: Optional[int] = None) -> Mesh:
    """The 1-D solver mesh: ``(n,) = ("solve",)`` over whatever exists.

    The axis every length-n dimension of the sharded Krylov engine
    (:mod:`repro.core.sharded`) shards over — solve vectors ``P("solve")``,
    ``(k, n)`` recycle bases ``P(None, "solve")``, operator data rows
    ``P("solve", ...)``.  Unlike :func:`make_production_mesh` there is no
    hard device-count requirement: ``n_devices=None`` takes every device
    jax sees (1 on a laptop CPU, 8 under
    ``xla_force_host_platform_device_count=8``, a full slice on TPU);
    an explicit count takes the first ``n_devices`` of them.
    """
    devices = jax.devices()
    n = len(devices) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"n_devices={n_devices} out of range: this process has "
            f"{len(devices)} devices"
        )
    return auto_mesh((n,), ("solve",), devices[:n])


def solve_state_shardings(mesh: Mesh) -> Any:
    """NamedSharding pytree for a :class:`repro.core.recycle.RecycleState`
    on the solve mesh — W/AW column-sharded along n, scalars replicated
    (the PartitionSpec rules live in :func:`repro.core.sharded.recycle_state_specs`)."""
    from repro.core import sharded as sharded_mod
    from repro.core.recycle import RecycleState

    s = sharded_mod.recycle_state_specs()
    # Explicit construction — PartitionSpec is a tuple subclass, so a
    # tree_map over a spec-valued pytree would descend into the specs.
    return RecycleState(
        W=NamedSharding(mesh, s.W),
        AW=NamedSharding(mesh, s.AW),
        theta=NamedSharding(mesh, s.theta),
        systems_solved=NamedSharding(mesh, s.systems_solved),
        drift=NamedSharding(mesh, s.drift),
    )


def solve_vector_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding of a flat length-n solve vector on the solve mesh."""
    from repro.core import sharded as sharded_mod

    return NamedSharding(mesh, sharded_mod.vector_spec())


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under dryrun.py (it sets xla_force_host_platform_device_count)"
        )
    return auto_mesh(shape, axes, devices[:n])


def axis_env_for(mesh: Mesh, *, batch_shardable: bool = True) -> Dict[str, Any]:
    """Logical-name binding for a mesh (see module docstring)."""
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    env: Dict[str, Any] = {
        "model": "model",
        "fsdp": dp_axes,
        "seq": None,
        "batch": dp_axes if batch_shardable else None,
    }
    if not batch_shardable:
        env["seq"] = "data"
    return env


def bind(mesh: Mesh, *, batch_shardable: bool = True) -> Dict[str, Any]:
    env = axis_env_for(mesh, batch_shardable=batch_shardable)
    shd.set_axis_env(env)
    return env


# ---------------------------------------------------------------------------
# Parameter sharding with ZeRO (fsdp) augmentation
# ---------------------------------------------------------------------------


def _fsdp_augment(spec: P, shape, env, stacked: bool) -> P:
    """Shard the first un-sharded, divisible dim of a ≥2-D leaf over fsdp.

    The stacked periods axis (dim 0 of scan-stacked leaves) is excluded:
    sharding the scan axis would force a full-stack all-gather every scan
    step instead of a per-layer one.
    """
    fsdp = env.get("fsdp")
    if not fsdp or len(shape) < 2:
        return spec
    size = int(np.prod([_axis_len(a) for a in fsdp])) if fsdp else 1
    dims = list(spec) + [None] * (len(shape) - len(spec))
    start = 1 if stacked else 0
    for i in range(start, len(dims)):
        if dims[i] is None and shape[i] % size == 0 and shape[i] >= size:
            dims[i] = fsdp
            return P(*dims)
    return spec


_AXIS_SIZES: Dict[str, int] = {}


def _axis_len(name: str) -> int:
    return _AXIS_SIZES.get(name, 1)


def param_shardings(mesh: Mesh, params_shapes, env) -> Any:
    """NamedSharding tree for a (possibly abstract) parameter tree."""
    global _AXIS_SIZES
    _AXIS_SIZES = dict(zip(mesh.axis_names, mesh.devices.shape))

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            out = [walk(v, path + (str(i),)) for i, v in enumerate(tree)]
            return type(tree)(out)
        stacked = any(p == "periods" for p in path)
        name = path[-1]
        ndim = len(tree.shape)
        base = _resolve_spec(name, ndim, stacked, env)
        full = _fsdp_augment(base, tree.shape, env, stacked)
        return NamedSharding(mesh, full)

    return walk(params_shapes, ())


def _resolve_spec(name: str, ndim: int, stacked: bool, env) -> P:
    dims: tuple = ()
    for suffix, d in shd._SUFFIX_DIMS.items():
        if name.endswith(suffix):
            dims = d
            break
    pad = ndim - len(dims) - (1 if stacked else 0)
    full = ((None,) if stacked else ()) + (None,) * max(pad, 0) + dims
    return P(*[env.get(d) if d else None for d in full[:ndim]])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_shardings(mesh: Mesh, batch_shapes, env) -> Any:
    """Shard (B, ...) input batches over the DP axes (dim 0)."""

    def one(leaf):
        b = env.get("batch")
        if b and len(leaf.shape) >= 1:
            size = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a] for a in (b if isinstance(b, tuple) else (b,))]))
            if leaf.shape[0] % size == 0:
                return NamedSharding(mesh, P(b, *([None] * (len(leaf.shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(one, batch_shapes)


def decode_state_shardings(mesh: Mesh, state_shapes, env) -> Any:
    """NamedShardings for a DecodeState (KV caches / SSM states / memory).

    Rules (leaf path → spec), with batch = env["batch"], seq = env["seq"]:
      *.ssd     (np, B, H, P, N)  → (None, batch, model, None, None)
      *.conv    (np, B, K-1, C)   → (None, batch, None, model)
      memory.*  (np, B, Hkv, S, d)→ (None, batch, None, None, None)
      cache k/v (np, B, Hkv, S, d)→ (None, batch, None, seq, None)
      length                       → replicated
    """

    def rule(path, leaf):
        ks = jax.tree_util.keystr(path)
        ndim = len(leaf.shape)
        batch = env.get("batch")
        seq = env.get("seq")
        if ".length" in ks or ndim == 0:
            return NamedSharding(mesh, P())
        if ".ssd" in ks:
            spec = (None, batch, "model", None, None)
        elif ".conv" in ks:
            spec = (None, batch, None, "model")
        elif "memory" in ks:
            spec = (None, batch, None, None, None)
        else:  # KV cache k / v
            spec = (None, batch, None, seq, None)
        spec = spec[:ndim]
        # drop axes that don't divide evenly (e.g. B=1 long decode)
        fixed = []
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = int(np.prod([sizes[a] for a in axes]))
            fixed.append(ax if dim % total == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(rule, state_shapes)
