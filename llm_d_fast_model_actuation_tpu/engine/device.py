"""TPU client release / reacquire — the mechanism behind chip time-sharing.

The reference's product premise is that a slept server frees its accelerator
for another server (docs/dual-pods.md:20-56; sleep actuation
inference-server.go:1710-1718). On GPU that falls out of CUDA contexts
coexisting; on TPU it does NOT: a process's PJRT client holds the chip
exclusively (a second process blocks in client init until the first exits).
So a TPU sleep that merely empties HBM still monopolizes the device.

This module tears the PJRT client down *in process* and re-creates it later:

  release_devices()   — drop all compiled-executable caches, then destroy
                        every live backend client. Caller must have deleted /
                        numpy-snapshotted every device array AND dropped
                        every reference to one first: a jax.Array, even a
                        deleted one, keeps its sharding, the sharding its
                        Device, the Device its client — and libtpu keeps the
                        chip's vfio group open for as long as the client
                        object lives. Returns whether the client really died.
  reacquire_devices() — re-initialize the backend (jax re-creates the PJRT
                        client on first use) and return the new devices. If
                        another process holds the chip this blocks/retries
                        until it is released — the hardware itself enforces
                        the one-awake-holder invariant the launcher's
                        ChipLedger tracks.

Compiled programs do not survive release (executables are client objects);
wake-path recompiles are served from the persistent XLA compile cache the
launcher arms before forking (launcher/main.py), so re-lowering is a disk
read, not a fresh XLA run.

Sharding objects also die with the client. `sharding_spec` / `rebuild_spec`
round-trip a sharding through a device-free description so state saved
before release can be restored onto the re-created devices (same process,
same device ordering).
"""

from __future__ import annotations

import gc
import logging
import time
import weakref
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.extend.backend  # submodule is not auto-imported by `import jax`
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

logger = logging.getLogger(__name__)


def release_devices() -> bool:
    """Destroy this process's backend clients (all platforms). Returns True
    when the default backend's client was really destroyed — only then has
    libtpu closed the chip and can another process open it. False (logged
    as an error) means some object still references the client: the chip is
    NOT free, though this process must still reacquire before using it."""
    client = weakref.ref(jax.extend.backend.get_backend())
    # Drop every cached executable first: live LoadedExecutables keep client
    # references, and tracing caches would hand back programs bound to the
    # dead client after re-init.
    jax.clear_caches()
    gc.collect()
    jax.extend.backend.clear_backends()
    # client <-> device objects form a cycle: only a collection frees them
    gc.collect()
    if client() is not None:
        logger.error(
            "backend client survived release (still referenced by: %s); "
            "the chip is NOT free for another process",
            sorted({type(r).__name__ for r in gc.get_referrers(client())}),
        )
        return False
    logger.info("released backend clients (TPU chip is now free)")
    return True


def reacquire_devices(
    timeout_s: float = 300.0, poll_s: float = 0.5
) -> Sequence[jax.Device]:
    """Re-create the backend client and return the fresh device list.

    Client init blocks while another process holds the chip; we retry until
    the deadline in case the platform surfaces contention as an error
    instead of a block.
    """
    deadline = time.monotonic() + timeout_s
    last: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            devs = jax.devices()
            logger.info("reacquired %d device(s): %s", len(devs), devs)
            return devs
        except Exception as e:  # init failed (chip busy) — retry
            last = e
            time.sleep(poll_s)
    raise TimeoutError(
        f"could not reacquire TPU devices within {timeout_s}s: {last}"
    )


# -- device-free sharding descriptions ---------------------------------------


def sharding_spec(x: jax.Array) -> Tuple[str, Any, Any, Any]:
    """A picklable, device-free description of ``x.sharding``."""
    s = x.sharding
    if isinstance(s, NamedSharding):
        return (
            "named",
            tuple(s.mesh.axis_names),
            tuple(s.mesh.devices.shape),
            tuple(s.spec),
        )
    return ("single", None, None, None)


def _device_array(mesh_shape: Tuple[int, ...]) -> np.ndarray:
    """Device array for a mesh shape, with the SAME ordering policy as
    `parallel.mesh.make_mesh`: topology-aware (`mesh_utils`) on real TPU so
    inner axes stay ICI-adjacent — and therefore identical to the pre-release
    mesh, keeping post-wake executables cache-compatible."""
    n = int(np.prod(mesh_shape))
    devices = jax.devices()[:n]
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        return mesh_utils.create_device_mesh(
            tuple(mesh_shape), devices=list(devices)
        )
    return np.asarray(devices).reshape(mesh_shape)


def rebuild_spec(spec: Tuple[str, Any, Any, Any]):
    """Rebuild a sharding from `sharding_spec` output on the CURRENT devices."""
    kind, axis_names, mesh_shape, pspec = spec
    if kind == "named":
        return NamedSharding(
            Mesh(_device_array(mesh_shape), axis_names), PartitionSpec(*pspec)
        )
    return SingleDeviceSharding(jax.devices()[0])


def rebuild_mesh(axis_names: Tuple[str, ...], mesh_shape: Tuple[int, ...]) -> Mesh:
    return Mesh(_device_array(mesh_shape), axis_names)
