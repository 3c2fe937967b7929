"""Production meshes. Functions, not module constants — importing this file
never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis of type Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16, 16) = ("data", "model").
    Multi-pod: 512 chips (2, 16, 16) = ("pod", "data", "model");
    each pod is one gossip data center (see DESIGN.md §4)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2):
    """Small mesh for subprocess tests with --xla_force_host_platform_device_count."""
    return make_mesh((data, model), ("data", "model"))


def seed_mesh(devices: int | str | None = "auto"):
    """1-D ``("seed",)`` mesh for device-sharding independent per-seed runs.

    The seed axis of `repro.api.run_batch` is embarrassingly parallel — each
    seed is its own private run — so the only mesh it needs is a flat row of
    devices. ``devices="auto"`` uses every local device; an int asks for
    exactly that many (error with an XLA_FLAGS hint when the host has fewer);
    ``None``, 0 or 1 returns None — the caller's cue to stay on the
    single-device vmap path.
    """
    avail = jax.local_device_count()
    if devices == "auto":
        devices = avail
    devices = int(devices or 0)
    if devices <= 1:
        return None
    if devices > avail:
        raise ValueError(
            f"seed_mesh: asked for {devices} devices but only {avail} are "
            f"visible; on a CPU host, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={devices} "
            f"before importing jax to fake a multi-device topology")
    return make_mesh((devices,), ("seed",))


def node_mesh(devices: int | str | None = "auto"):
    """1-D ``("node",)`` mesh for sharding the gossip node axis.

    Same semantics as `seed_mesh`: ``"auto"`` takes every local device, an
    int asks for exactly that many (error with the XLA_FLAGS hint when the
    host has fewer), and ``None``/0/1 returns None — the caller's cue to
    stay on the unsharded path. Unlike seeds, node shards are NOT
    independent: the sharded chunk program exchanges boundary theta~ between
    neighbors with `lax.ppermute` (see `repro.api.shard_node`).
    """
    avail = jax.local_device_count()
    if devices == "auto":
        devices = avail
    devices = int(devices or 0)
    if devices <= 1:
        return None
    if devices > avail:
        raise ValueError(
            f"node_mesh: asked for {devices} devices but only {avail} are "
            f"visible; on a CPU host, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={devices} "
            f"before importing jax to fake a multi-device topology")
    return make_mesh((devices,), ("node",))


def seed_node_mesh(seed_devices: int | None = 1,
                   node_devices: int | str | None = "auto"):
    """2-D ``("seed", "node")`` grid: independent seed rows x node columns.

    `repro.api.run_batch` shards the vmapped seed axis over the rows and
    each seed's node axis over the columns. ``node_devices="auto"`` spreads
    whatever devices remain after the seed rows (avail // seed_devices);
    node_devices <= 1 returns None — fall back to `seed_mesh` / vmap.
    """
    avail = jax.local_device_count()
    s = int(seed_devices or 1) or 1
    if node_devices == "auto":
        node_devices = avail // s
    nd = int(node_devices or 0)
    if nd <= 1:
        return None
    if s * nd > avail:
        raise ValueError(
            f"seed_node_mesh: asked for {s} x {nd} = {s * nd} devices but "
            f"only {avail} are visible; on a CPU host, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={s * nd} "
            f"before importing jax to fake a multi-device topology")
    return make_mesh((s, nd), ("seed", "node"))


def gossip_axes(mesh) -> tuple[str, ...]:
    """Which mesh axes carry the gossip node dimension."""
    return ("pod",) if "pod" in mesh.axis_names else ("data",)


def gossip_nodes(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in gossip_axes(mesh)]))


def data_axes_for_batch(mesh) -> tuple[str, ...]:
    """Axes the *within-node* batch dim shards over."""
    return ("data",) if "pod" in mesh.axis_names else ()
