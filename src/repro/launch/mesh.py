"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
data parallelism (FSDP) by default and the cross-pod gradient reduction
(optionally int8-compressed, train/optimizer.py).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Mesh over the visible devices with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` (dist/sharding.py) and the decoder's
    GSPMD lane sharding do not accept; every mesh of this repo is built
    here so the axis type is decided in one place.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: Optional[int] = None, model: int = 1):
    """Small mesh over the locally visible devices (tests / examples)."""
    n = n or len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))


# -- host-aware meshes (multi-host launch, repro.launch.multihost) ----------

def make_local_data_mesh():
    """1-D "data" mesh over THIS process's devices only.

    The multi-host decoder's per-host stage runs here: chunk lanes shard
    over the local chips while the compressed bytes stay host-resident.
    Built from ``jax.local_devices()`` directly (``jax.make_mesh`` would
    claim the whole cluster).
    """
    import numpy as np
    return jax.sharding.Mesh(np.array(jax.local_devices()), ("data",))


def make_global_data_mesh():
    """1-D "data" mesh over every device of every process."""
    return make_mesh((jax.device_count(),), ("data",))


def make_hosts_mesh():
    """("hosts", "local") mesh: axis 0 enumerates processes.

    Device rows are grouped by ``process_index`` so a ``P("hosts")``
    sharding gives each host one contiguous block, replicated over its
    local devices — the layout :func:`repro.launch.multihost.
    assemble_global_coeffs` uses to stitch per-host decodes into one
    global batch without any collective.
    """
    import numpy as np
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    per_host = len(devs) // max(1, jax.process_count())
    arr = np.array(devs).reshape(jax.process_count(), per_host)
    return jax.sharding.Mesh(arr, ("hosts", "local"))


# Hardware constants for the roofline analysis (TPU v5e).
TPU_V5E = {
    "peak_flops_bf16": 197e12,   # FLOP/s per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link (one direction)
    "hbm_bytes": 16e9,           # capacity per chip
    "vmem_bytes": 128 * 2**20,
}
