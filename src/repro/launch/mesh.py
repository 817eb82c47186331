"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests/benches must keep seeing 1 device.

Topology (TPU v5e): one pod = 16×16 = 256 chips → axes ('data', 'model');
two pods = 512 chips → axes ('pod', 'data', 'model').  The 'pod' axis is
DCN-connected (slower links); by default it carries data parallelism (the
gradient all-reduce tolerates DCN latency); the launcher can instead run
pipeline stages over it (train/pipeline.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """A mesh with Auto axes: the executor places activations through
    ``with_sharding_constraint``, which only Auto axes accept."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Whatever devices exist on this host, as a ('data','model') mesh.

    ``model`` splits off a tensor-parallel axis (must divide the device
    count); the default keeps everything data-parallel — used by CPU
    examples, forced-host-device tests, and the sharded serve smoke.
    """
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        raise ValueError(f"model={model} does not divide {n} devices")
    return _mesh((n // model, model), ("data", "model"))


def mesh_info(mesh) -> dict:
    return {"shape": dict(mesh.shape),
            "devices": int(mesh.devices.size),
            "axis_names": list(mesh.axis_names)}
