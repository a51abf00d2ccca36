"""Device mesh construction.

Replaces the reference's network config (`config/network.json` +
/root/reference/src/config.rs:5-9): where the reference enumerates worker
socket addresses, the TPU build enumerates devices on one axis of a
jax.sharding.Mesh. Multi-host extension happens by initializing
jax.distributed and letting jax.devices() span hosts (DCN), with the same
mesh axis semantics.
"""

import contextlib

import numpy as np
import jax

SHARD_AXIS = "shards"


def pallas_guard(mesh):
    """Context manager for TRACING mesh programs: disables the Pallas
    mont_mul dispatch unless the mesh's own devices are TPUs.

    field_jax._use_pallas keys off jax.default_backend(), which is the
    PROCESS default — a virtual CPU mesh traced in a TPU-default process
    (make_mesh(platform="cpu") on a machine with a chip) would otherwise
    emit Mosaic pallas_calls that cannot lower for CPU execution. On a
    real TPU mesh this is a no-op and the kernels stay."""
    from ..backend import field_jax as FJ

    if mesh.devices.ravel()[0].platform == "tpu":
        return contextlib.nullcontext()
    return FJ.pallas_disabled()


def init_multihost(coordinator, num_processes, process_id,
                   local_device_ids=None):
    """Join a multi-host (DCN) mesh group: after this, jax.devices() spans
    every host and make_mesh() builds cross-host meshes whose collectives
    ride ICI within a pod and DCN across pods.

    This is the multi-controller replacement for the reference's
    dispatcher->worker star + worker<->worker peer mesh
    (/root/reference/config/network.json, src/worker.rs:441-536): instead
    of one coordinator driving RPC fan-outs, every host runs the same
    program and XLA inserts the cross-host collectives.

    coordinator: "host:port" of process 0 (the network.json analog).
    """
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    return jax.process_count(), jax.device_count()


def make_submesh(devices):
    """1-D mesh over an EXPLICIT device list — the placement scheduler's
    construction hook (service/placement.py): it partitions jax.devices()
    into disjoint leased submeshes, and each lease's big sharded prove
    runs on a Mesh built from exactly its devices, so concurrent
    submeshes never contend for a chip. The device list should be
    ICI-contiguous (the leaser hands out contiguous runs of the
    enumeration order) for collective locality."""
    devs = list(devices)
    assert devs, "submesh needs at least one device"
    return jax.sharding.Mesh(np.array(devs), (SHARD_AXIS,))


def make_mesh(n_devices=None, platform=None):
    """1-D mesh over the first n_devices (default: all) devices.

    platform: None = jax's default backend. Pass platform="cpu" to build
    the N-device virtual host mesh
    (--xla_force_host_platform_device_count) in a process whose default
    backend is a TPU.
    """
    devs = jax.devices(platform) if platform else jax.devices()
    if n_devices is not None:
        assert len(devs) >= n_devices, (
            f"need {n_devices} {platform or 'default'} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (SHARD_AXIS,))
