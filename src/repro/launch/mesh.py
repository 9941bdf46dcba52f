"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run forces 512 host devices via XLA_FLAGS before any import).

Mesh shapes: single pod = (data=16, model=16) — 256 chips (one v5e pod);
multi-pod adds an outer pure-DP "pod" axis = (pod=2, data=16, model=16).
The same logical-axis rule table resolves model configs onto either mesh
(the cluster-scale VLA contract, DESIGN.md §2).
"""

from __future__ import annotations

import math
import os
import warnings

import jax


def force_host_devices(n: int) -> None:
    """Force ``n`` host-platform XLA devices (the multi-device-CPU testing
    pattern).  MUST run before jax initializes its backend — call it first
    thing in main(), before any jax array/device touch.  No-op when n <= 1
    or the flag is already set (e.g. by the CI job's environment)."""
    if n <= 1 or "--xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        return
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={n}")


def parse_mesh(spec: str) -> tuple[int, int]:
    """Parse a ``--mesh DxM`` spec ("4x2" -> (4, 2)): data axis x model axis."""
    try:
        d, m = spec.lower().split("x")
        d, m = int(d), int(m)
    except ValueError:
        raise ValueError(f"mesh spec {spec!r} is not DxM (e.g. '4x2')")
    if d < 1 or m < 1:
        raise ValueError(f"mesh spec {spec!r} must have positive axes")
    return d, m


def make_production_mesh(*, multi_pod: bool = False):
    """The full-scale mesh — or, on a dev box with fewer devices, the largest
    mesh the available devices support (axes halved largest-first, with a
    warning), so ``launch/serve.py --mesh`` runs anywhere the tests do."""
    shape = [2, 16, 16] if multi_pod else [16, 16]
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devs = jax.devices()
    if len(devs) < math.prod(shape):
        want = math.prod(shape)
        while math.prod(shape) > len(devs):
            i = max(range(len(shape)), key=lambda j: shape[j])
            if shape[i] == 1:
                break
            shape[i] //= 2
        warnings.warn(
            f"{want}-device production mesh degraded to {tuple(shape)} over "
            f"{axes} ({len(devs)} devices available; force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={want})",
            RuntimeWarning, stacklevel=2)
    need = math.prod(shape)
    return _auto_mesh(tuple(shape), axes, devs[:need])


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (2,2) on 4 forced devices)."""
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(f"mesh {shape} needs {need} devices, have {len(devs)}")
    return _auto_mesh(tuple(shape), axes, devs[:need])


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules place
    arrays through ``with_sharding_constraint`` and leave propagation to
    GSPMD, which the default *explicit* axis types (JAX >= 0.7) reject."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_shard_count(mesh) -> int:
    """Number of ways the batch/token axis is sharded (pod x data)."""
    n = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            n *= mesh.shape[ax]
    return n
