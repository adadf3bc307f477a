"""Chip identity translation: chip IDs <-> local indices / env injection.

TPU edition of the reference's `gputranslator.py` (3-tier mode selection,
docs/launcher.md:656-696):

  1. **chip-map mock** — a chip-map ConfigMap-shaped source (file or dict)
     keyed by NODE_NAME: the shared source of truth for hardware-less e2e;
  2. **naive mock** — N synthetic chips in a row topology;
  3. **real** — enumerate local TPU chips via the native telemetry shim
     (``native/tpuinfo``, ctypes); the mode names the source the shim used
     (``real:pci+vfio`` on a Cloud TPU v5e host, ``real:pci``,
     ``real:devfs``), and a host where it finds nothing fails.

Unlike the GPU original (flat UUID->index), the translator exposes the host
*topology* so placement can demand ICI-contiguous sub-slices.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

from ..parallel.topology import ChipMap, HostTopology

logger = logging.getLogger(__name__)


class ChipTranslator:
    def __init__(self, host: HostTopology, mode: str) -> None:
        self._host = host
        self.mode = mode

    # -- constructors --------------------------------------------------------

    @classmethod
    def create(
        cls,
        mock_chips: bool = False,
        mock_chip_count: int = 8,
        mock_topology: str = "",
        chip_map_path: Optional[str] = None,
        node_name: Optional[str] = None,
    ) -> "ChipTranslator":
        """Mode selection, highest priority first: chip-map mock -> naive
        mock -> real hardware."""
        if mock_chips:
            node = node_name or os.environ.get("NODE_NAME", "")
            path = chip_map_path or os.environ.get("CHIP_MAP_PATH", "")
            if node and path and os.path.exists(path):
                with open(path) as f:
                    data = json.load(f)
                cm = ChipMap.parse(data)
                host = cm.host(node)
                if host is not None:
                    logger.info("chip-map mock: node %s, %s chips", node, len(host.chips))
                    return cls(host, mode="chip-map-mock")
                logger.warning("node %s not in chip map %s; naive fallback", node, path)
            topo = mock_topology or _default_topology(mock_chip_count)
            host = HostTopology.make(topo, node=node or "mock")
            logger.info("naive mock: %s chips (topology %s)", len(host.chips), topo)
            return cls(host, mode="naive-mock")
        host, source = enumerate_real()
        logger.info("real chips via %s: %s", source, [c.chip_id for c in host.chips])
        return cls(host, mode=f"real:{source}")

    # -- queries -------------------------------------------------------------

    @property
    def host(self) -> HostTopology:
        return self._host

    def chip_ids(self) -> List[str]:
        return [c.chip_id for c in self._host.chips]

    def id_to_index(self, chip_id: str) -> int:
        info = self._host.by_id().get(chip_id)
        if info is None:
            raise KeyError(f"unknown chip id {chip_id!r}")
        return info.index

    def env_for(self, chip_ids: Sequence[str]) -> Dict[str, str]:
        """Env vars pinning an engine process to `chip_ids`."""
        return self._host.visible_devices_env(chip_ids)


def _default_topology(n: int) -> str:
    if n >= 8 and n % 4 == 0:
        return f"{n // 4}x4"
    return str(n)


def enumerate_real() -> Tuple[HostTopology, str]:
    """The chips this host may open, and the source the native shim found
    them through. Raises where the shim is not built or finds no chip — a
    launcher that guessed would pin engines to chips that are not there."""
    from ..native import tpuinfo
    from ..parallel.topology import ChipInfo

    doc = tpuinfo.query()
    chips, source = doc.get("chips", []), doc.get("source", "")
    if not chips:
        raise RuntimeError(
            f"no TPU chips found (tpuinfo source {source!r}: no Google PCI "
            "function with an openable /dev/vfio group, no /dev/accel*); "
            "use a mock backend (launcher: --mock-chips, requester: "
            "--backend static/env) for hardware-less operation"
        )
    topo = doc.get("topology") or _default_topology(len(chips))
    host = HostTopology.make(topo, node=os.environ.get("NODE_NAME", "local"))
    host.chips = [  # keep shim-reported IDs
        ChipInfo(
            chip_id=c["chip_id"],
            index=c["index"],
            coords=tuple(c.get("coords", ())),
        )
        for c in chips
    ]
    return host, source
