"""Production, host and serving meshes + sharding helpers.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the `pod` axis is an outer
data-parallel axis whose gradient all-reduce is the only cross-DCI collective.
Serving:    (data=d, model=m) over however many devices the host exposes —
            on CPU, XLA_FLAGS=--xla_force_host_platform_device_count=N forces
            N host devices, which is how the sharded serving path is tested
            without hardware (see docs/sharding.md).

Defined as functions so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axes.  The model code shards through
    GSPMD propagation plus shard_ctx constraints, not through sharding in
    types, which jax.make_mesh's default Explicit axes would turn on."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples): (1, N)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_serve_mesh(data: int = 1, model: int = 1,
                    devices: Optional[Sequence] = None) -> Mesh:
    """(data, model) serving mesh over an explicit device subset.

    Unlike jax.make_mesh this takes the devices directly, so tests can build
    1-, 2- and 4-device meshes side by side from one forced-host-device
    process (the device-count parametrization in tests/test_sharding.py).
    """
    need = data * model
    devs = list(devices) if devices is not None else jax.devices()[:need]
    if len(devs) < need:
        raise ValueError(f"mesh ({data}, {model}) needs {need} devices, "
                         f"have {len(devs)}; on CPU set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={need}")
    return Mesh(np.asarray(devs[:need]).reshape(data, model),
                ("data", "model"))


def ensure_host_devices(n: int) -> None:
    """Force n host CPU devices if no count is already forced. Must run
    before jax's backend initializes — which is lazy, so before the first
    device query / array op, not before `import jax`."""
    import os
    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and flag not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}={n}".strip()


def parse_mesh_spec(spec: str) -> Tuple[int, int]:
    """'4' -> (1, 4) tensor-parallel; 'DxM' (e.g. '2x2') -> (D, M)."""
    s = spec.lower().strip()
    try:
        if "x" in s:
            d, m = s.split("x")
            d, m = int(d), int(m)
        else:
            d, m = 1, int(s)
    except ValueError as e:
        raise ValueError(f"bad mesh spec {spec!r}; want 'M' or 'DxM'") from e
    if d < 1 or m < 1:
        raise ValueError(f"bad mesh spec {spec!r}: axes must be >= 1")
    return d, m


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def named_shardings(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree (for device_put and jit
    shardings outside a `jax.set_mesh` context). is_leaf keeps each
    PartitionSpec whole."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))
