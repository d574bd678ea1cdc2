"""Production mesh definitions.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis
carries only data parallelism + gradient reduction (the slow DCN/ICI
tier), everything latency-sensitive stays inside a pod.

Defined as functions, not module constants, so importing never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
Every axis is `Auto`: the model code places arrays through sharding
constraints (`repro.pspec.constrain`) and lets the compiler propagate
the rest, which `jax.make_mesh`'s default `Explicit` axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names, for CPU tests."""
    return _mesh((1, 1), ("data", "model"))


def make_campaign_mesh(n_devices: int | None = None):
    """1-D "campaign" mesh for sharded replay campaigns
    (`sim_engine.SimEngine(mesh=...)`): the (trace x tenant-mix)
    leading axis of a campaign partitions across it, every other
    campaign axis stays device-local.  Defaults to ALL visible
    devices; `n_devices` clamps to a prefix (n_devices=1 is the
    degenerate mesh the parity tests pin against the unsharded path).
    On CPU, `XLA_FLAGS=--xla_force_host_platform_device_count=N`
    (set before first jax init) fans one host out to N devices."""
    devs = jax.devices()
    if n_devices is not None:
        assert 1 <= n_devices <= len(devs), (n_devices, len(devs))
        devs = devs[:n_devices]
    return _mesh((len(devs),), ("campaign",), devices=devs)
