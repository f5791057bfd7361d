"""Device meshes, all with Auto axes.

`local_mesh` / `make_debug_mesh` span the devices present (the train
driver's mesh). `make_production_mesh` builds the 256/512-chip v5e pod
meshes for the dry-run lowering only; `dryrun.py` sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
These are functions, so importing this module never touches device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)

# hardware constants (TPU v5e) for the roofline
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False,
                         shape=None) -> jax.sharding.Mesh:
    """`shape` overrides the (data, model) — or (pod, data, model) — split;
    total chips stay 256/pod. The TP-vs-FSDP balance is a first-class perf knob
    (see EXPERIMENTS.md §Perf)."""
    shape = shape or (MULTI_POD if multi_pod else SINGLE_POD)
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices (set XLA_FLAGS=--xla_force_host_platform_device_count=512), "
            f"have {len(devices)}")
    return auto_mesh(shape, axes, devices[:n])


def make_debug_mesh(n_data: int = 1, n_model: int = 1) -> jax.sharding.Mesh:
    """(data, model) mesh over the first ``n_data * n_model`` devices."""
    return auto_mesh((n_data, n_model), ("data", "model"),
                      jax.devices()[: n_data * n_model])


def local_mesh() -> jax.sharding.Mesh:
    """(data, model) mesh with every device present on the data axis."""
    return make_debug_mesh(len(jax.devices()), 1)


def auto_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    """Mesh with Auto axes: the model code places activations with
    `with_sharding_constraint`, which only Auto axes accept."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))
