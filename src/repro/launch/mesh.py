"""Mesh construction: the TPU-v5e production meshes and the host mesh.

Production (TPU v5e target):

  Single-pod : (data=16, model=16)            = 256 chips
  Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

DP spans pod x data (gradient reduction hierarchical: reduce-scatter in-pod
over ICI, all-reduce across pods over DCI — optionally MixFP4-compressed,
see distributed/gradcomp.py).  TP/EP live on the in-pod 'model' axis.
Multi-pod specs are NOT written by hand — model code says 'data' and
``distributed.sharding.prepend_pod`` rewrites it to ('pod', 'data'), so DP
spans pods while model/TP stays in-pod; specs destined for explicit jit
in_shardings then pass ``distributed.sharding.sanitize_specs``, which
replicates any dim the mesh axes don't divide exactly (GSPMD pads internal
constraints, explicit in_shardings don't).

The host mesh is the same (data, model) axis naming over whatever devices
this host actually has — the mesh for tests, examples, elastic restarts,
and the docs/sharding.md cookbook: code written against
``make_host_mesh(model=N)`` (e.g. sharded packed serving,
``ServeEngine(mesh=...)``) moves to ``make_production_mesh()`` unchanged
because every spec names the same axes.  On CPU, fake N devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
initializes (``launch/serve.py --force-host-devices N`` does this).

Everything here is a FUNCTION so importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialisation).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def _mesh(shape, axes):
    """Auto axes: the model code places values with
    ``with_sharding_constraint``, which refuses the Explicit axes that
    ``jax.make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small (data, model) mesh over whatever devices this host actually
    has (tests, examples, elastic restarts on fewer chips).  ``model=N``
    carves an N-way model axis for host-scale TP — the sharded packed
    serving path (docs/sharding.md) — and the data axis absorbs the
    rest."""
    n = jax.device_count()
    if model < 1 or model > n or n % model:
        raise ValueError(
            f"host has {n} device(s); cannot carve a {model}-way model "
            f"axis (on CPU, fake devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N before jax "
            f"initializes — launch/serve.py --force-host-devices N)")
    if data is None:
        data = n // model
    return _mesh((data, model), ("data", "model"))
