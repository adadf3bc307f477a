"""Raw host<->device transfer bandwidth measurement.

bench.py's `host_device_*_gibps` numbers: the link ceiling (PCIe on a TPU
host) for checkpoint load and release-cycle numbers.
"""

from __future__ import annotations

import time
from typing import Tuple


def measure_host_device_bandwidth(mib: int = 256) -> Tuple[float, float]:
    """Returns (host_to_device_gibps, device_to_host_gibps) for one `mib`
    MiB float32 transfer each way. The probe buffers are freed before
    returning."""
    import jax
    import numpy as np

    x_host = np.ones((mib, 1024, 256), np.float32)  # mib MiB
    t0 = time.monotonic()
    x_dev = jax.block_until_ready(jax.device_put(x_host))
    h2d = (mib / 1024) / max(time.monotonic() - t0, 1e-9)
    t0 = time.monotonic()
    np.asarray(x_dev)
    d2h = (mib / 1024) / max(time.monotonic() - t0, 1e-9)
    x_dev.delete()
    del x_host
    return h2d, d2h
