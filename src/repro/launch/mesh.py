"""Production + serving mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init.

Single pod : (16, 16)    axes ("data", "model")        — 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — 512 chips / 2 pods
Serving    : (d, m)      axes ("data", "model")        — m shards the KV
             arena along kv heads (repro.serving, DESIGN.md §Serving
             ¶Multi-device); on a CPU host the device pool comes from
             the same forced-host-platform trick the dry-run uses.

The "pod" axis carries data parallelism across the DCN boundary (gradient
all-reduce spans pods); "model" carries TP/EP/sequence-sharding inside a
pod's ICI domain.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto(len(axes)))


def make_host_mesh():
    """1-device mesh with the same axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), **_auto(2))


def make_serving_mesh(n_model: int = 0, *, n_data: int = 1):
    """("data", "model") mesh for the multi-device serving engine.

    `n_model` is the KV-shard width (0 = every device not claimed by
    `n_data`).  Raises when the platform exposes fewer devices than the
    mesh needs: a CPU run forces its host devices itself
    (`--xla_force_host_platform_device_count`) before jax starts.
    """
    n_dev = jax.device_count()
    n_data = max(1, n_data)
    if n_model <= 0:
        n_model = max(1, n_dev // n_data)
    if n_data * n_model > n_dev:
        raise ValueError(
            f"serving mesh ({n_data}, {n_model}) needs "
            f"{n_data * n_model} devices, {jax.default_backend()} has "
            f"{n_dev}"
        )
    return jax.make_mesh((n_data, n_model), ("data", "model"), **_auto(2))
