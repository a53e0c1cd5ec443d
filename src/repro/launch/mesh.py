"""Production meshes.

``make_production_mesh`` is exactly the spec'd function (a FUNCTION, not a
module-level constant — importing this module never touches jax device
state). ``make_cluster_mesh`` derives the DiLoCoX view of the same devices:
a leading "clusters" axis (the 1 Gbps decentralized boundary — the pod axis
when multi-pod, a split of the data axis when single-pod) plus the intra-
cluster ("data", "model") axes.
"""
from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

_HOST_FLAG = "--xla_force_host_platform_device_count"


def request_host_devices(n: Optional[int]) -> None:
    """CPU test path: ask XLA's host platform for ``n`` devices.  Must run
    before jax initializes a backend; an accelerator ignores the flag, and
    ``launcher_mesh`` then holds ``n`` against the real device count."""
    flags = os.environ.get("XLA_FLAGS", "")
    if n and _HOST_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_HOST_FLAG}={n}".strip()


def launcher_mesh(shape: Sequence[int], axes: Sequence[str],
                  devices: Optional[int] = None) -> Mesh:
    """The launchers' mesh over the devices jax sees.  ``devices`` (the
    ``--devices`` flag) must agree with their count when given.  A mesh
    smaller than the host takes its first devices (a 2-chip run on a
    4-chip host); one process drives every chip either way.  Axes are
    ``Auto``: the launchers place arrays with GSPMD sharding rules and
    constraints, which jax 0.9's default ``Explicit`` axes reject."""
    devs = jax.devices()
    where = f"{len(devs)} {devs[0].platform} device(s)"
    if devices is not None and devices != len(devs):
        raise SystemExit(f"--devices {devices} disagrees with the {where} "
                         f"jax sees")
    n = math.prod(shape)
    if n > len(devs):
        raise SystemExit(f"mesh {dict(zip(axes, shape))} needs {n} "
                         f"devices; jax sees {where}")
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_cluster_mesh(base: Mesh, n_clusters: Optional[int] = None) -> Mesh:
    """DiLoCoX view: ("clusters", "data", "model").

    multi-pod base (pod, data, model): clusters = pods (slow links cross
    pods only). single-pod base (data, model): the data axis is split into
    (clusters, data) — by default 2 clusters x 8-way intra-cluster DP,
    matching the paper's several-clusters-per-site topology.
    """
    devs = base.devices
    if devs.ndim == 3:              # multi-pod
        if n_clusters not in (None, devs.shape[0]):
            raise ValueError("multi-pod clusters == pods")
        return Mesh(devs, ("clusters", "data", "model"))
    n_clusters = n_clusters or 2
    d_total, m = devs.shape
    if d_total % n_clusters:
        raise ValueError(f"data axis {d_total} not divisible by "
                         f"{n_clusters} clusters")
    reshaped = devs.reshape(n_clusters, d_total // n_clusters, m)
    return Mesh(reshaped, ("clusters", "data", "model"))


def make_serving_mesh(base: Mesh) -> Mesh:
    """Serving has no cluster boundary: flatten pods into the batch axis."""
    devs = base.devices
    if devs.ndim == 3:
        p, d, m = devs.shape
        return Mesh(devs.reshape(p * d, m), ("data", "model"))
    return Mesh(devs, ("data", "model"))


def describe(mesh: Mesh) -> str:
    return f"{dict(zip(mesh.axis_names, mesh.devices.shape))}"
