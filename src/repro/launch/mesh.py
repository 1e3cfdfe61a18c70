"""Production meshes. Functions, not module constants: importing this must
never touch jax device state (the dry-run sets XLA_FLAGS first)."""

from __future__ import annotations

import jax

from repro.kernels import compat


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod: 16x16 = 256 chips; multi-pod: 2 pods = 512 chips.

    Axes: 'data' carries batch (DP/FSDP/ZeRO), 'model' carries TP/EP/SP.
    The 'pod' axis extends DP across the inter-pod DCN/ICI boundary --
    gradient all-reduces hierarchically decompose (intra-pod reduce-scatter
    + inter-pod all-reduce on the pod axis), which XLA emits automatically
    for P(('pod','data')) sharded batches.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(model: int = 1, devices=None):
    """("data", "model") mesh over this host's devices (default: all)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    assert n % model == 0
    return compat.make_mesh((n // model, model), ("data", "model"),
                            devices=devices)
