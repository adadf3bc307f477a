"""TPU chip identity and slice-topology model.

Replaces the reference's flat GPU-UUID space (`gputranslator.py`, the
`gpu-map` ConfigMap, `CUDA_VISIBLE_DEVICES` injection) with a topology-aware
chip model: every chip has a stable ID, a local index, and ICI mesh
coordinates. Placement must respect the physical mesh — a 2x2 sub-slice of a
2x4 host is ICI-contiguous, an arbitrary 4-chip subset is not.

Reference parity:
  gpu_uuids -> CUDA_VISIBLE_DEVICES   (launcher.py:175-191)
  gpu-map ConfigMap node->"index uuid" lines (controller.go:888-924)
becomes
  chip_ids -> TPU_VISIBLE_DEVICES (+ process-bounds env)
  chip-map ConfigMap node->"index chip_id x,y[,z]" lines
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..api.types import SliceTopology


@dataclass(frozen=True)
class ChipInfo:
    """One TPU chip on one host."""

    chip_id: str  #: stable identity, e.g. "tpu-4c:0:0" or a PCI serial
    index: int  #: local device index (order of TPU_VISIBLE_DEVICES)
    coords: Tuple[int, ...] = ()  #: ICI mesh coordinates within the slice


@dataclass
class HostTopology:
    """The TPU complement of one host (one launcher's domain).

    E.g. a v5e-8 host is topology 2x4: 8 chips, coords (x, y) with
    x in 0..1, y in 0..3.
    """

    topology: SliceTopology
    chips: List[ChipInfo] = field(default_factory=list)

    @classmethod
    def make(cls, topology: str, node: str = "local") -> "HostTopology":
        topo = SliceTopology.parse(topology)
        chips: List[ChipInfo] = []
        for i in range(topo.num_chips):
            coords = _unravel(i, topo.dims)
            cid = f"tpu-{node}-" + "-".join(str(c) for c in coords)
            chips.append(ChipInfo(chip_id=cid, index=i, coords=coords))
        return cls(topology=topo, chips=chips)

    def by_id(self) -> Dict[str, ChipInfo]:
        return {c.chip_id: c for c in self.chips}

    def indices_for(self, chip_ids: Sequence[str]) -> List[int]:
        """chip IDs -> local indices (the TPU_VISIBLE_DEVICES value),
        preserving request order. KeyError on unknown ID."""
        m = self.by_id()
        return [m[cid].index for cid in chip_ids]

    def visible_devices_env(self, chip_ids: Sequence[str]) -> Dict[str, str]:
        """Env vars pinning an engine process to `chip_ids`.

        The TPU analogue of the reference's CUDA_VISIBLE_DEVICES injection
        (inference-server.go:1916-1923). Also sets process/chip bounds so
        multiple engine processes can share one host without the device
        plugin arbitrating, and lifts libtpu's one-process-per-host
        lockfile (/tmp/libtpu_lockfile, held until process exit — also
        across a device release): the launcher's ChipLedger is what keeps
        two awake engines off one chip. Checked on libtpu 0.0.34, v5e.
        """
        by_id = self.by_id()
        chips = [by_id[cid] for cid in chip_ids]
        env = {
            "TPU_VISIBLE_DEVICES": ",".join(
                str(i) for i in sorted(c.index for c in chips)
            ),
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": _chips_bounds(
                [c.coords for c in chips], self.topology.dims
            ),
            # Engine-side identity for the cooperative HBM-usage protocol
            # (native/hbm_publisher.py) — the chips this process accounts to.
            "FMA_CHIP_IDS": ",".join(c.chip_id for c in chips),
        }
        return env


def _unravel(i: int, dims: Tuple[int, ...]) -> Tuple[int, ...]:
    coords = []
    for d in reversed(dims):
        coords.append(i % d)
        i //= d
    return tuple(reversed(coords))


def _chips_bounds(coords: List[Tuple[int, ...]], dims: Tuple[int, ...]) -> str:
    """Bounding box of the chosen coords, padded to 3 axes (libtpu grammar)."""
    if not coords:
        return "1,1,1"
    ndim = len(dims)
    spans = []
    for ax in range(ndim):
        vals = [c[ax] for c in coords]
        spans.append(max(vals) - min(vals) + 1)
    while len(spans) < 3:
        spans.append(1)
    return ",".join(str(s) for s in spans[:3])


def contiguous(coords: List[Tuple[int, ...]]) -> bool:
    """Whether a chip set forms a dense axis-aligned sub-box (ICI-contiguous).

    TPU-specific placement constraint with no GPU-reference equivalent: TP
    collectives ride ICI only if the chips are a contiguous sub-mesh.
    """
    if not coords:
        return True
    ndim = len(coords[0])
    vol = 1
    for ax in range(ndim):
        vals = [c[ax] for c in coords]
        vol *= max(vals) - min(vals) + 1
    return vol == len(set(coords))


def assign_chips(
    host: HostTopology,
    free_ids: Sequence[str],
    count: int,
    topology: str = "",
) -> Optional[List[str]]:
    """Pick `count` free chips forming an ICI-contiguous sub-slice.

    The reference's allocation emulation picks random free UUIDs
    (cmd/test-requester/gpu-allocation.go:41-257); on TPU a placement is only
    valid if the chips are ICI-connected, and if `topology` is given the
    bounding box must match it. Returns chip IDs or None if infeasible.
    """
    want_topo = SliceTopology.parse(topology) if topology else None
    if want_topo and want_topo.num_chips != count:
        raise ValueError(
            f"topology {topology} has {want_topo.num_chips} chips, want {count}"
        )
    free = [c for c in host.chips if c.chip_id in set(free_ids)]
    if len(free) < count:
        return None
    # Enumerate axis-aligned sub-boxes of volume `count` over the host dims,
    # smallest surface first (keeps future allocations contiguous too).
    dims = host.topology.dims
    boxes = _boxes_of_volume(dims, count)
    if want_topo:
        want = tuple(sorted(want_topo.dims + (1,) * (len(dims) - len(want_topo.dims))))
        boxes = [b for b in boxes if tuple(sorted(b)) == want]
    free_coords = {c.coords for c in free}
    by_coords = {c.coords: c for c in free}
    for box in boxes:
        for origin in _origins(dims, box):
            cells = _box_cells(origin, box)
            if all(c in free_coords for c in cells):
                return [by_coords[c].chip_id for c in cells]
    return None


def _boxes_of_volume(dims: Tuple[int, ...], vol: int) -> List[Tuple[int, ...]]:
    out: List[Tuple[int, ...]] = []

    def rec(ax: int, remaining: int, acc: List[int]) -> None:
        if ax == len(dims):
            if remaining == 1:
                out.append(tuple(acc))
            return
        for d in range(1, min(dims[ax], remaining) + 1):
            if remaining % d == 0:
                rec(ax + 1, remaining // d, acc + [d])

    rec(0, vol, [])
    # prefer compact boxes (least max extent)
    out.sort(key=lambda b: (max(b), b))
    return out


def _origins(dims: Tuple[int, ...], box: Tuple[int, ...]):
    ranges = [range(d - b + 1) for d, b in zip(dims, box)]

    def rec(ax: int, acc: List[int]):
        if ax == len(dims):
            yield tuple(acc)
            return
        for o in ranges[ax]:
            yield from rec(ax + 1, acc + [o])

    yield from rec(0, [])


def _box_cells(origin: Tuple[int, ...], box: Tuple[int, ...]):
    def rec(ax: int, acc: List[int]):
        if ax == len(origin):
            yield tuple(acc)
            return
        for o in range(box[ax]):
            yield from rec(ax + 1, acc + [origin[ax] + o])

    return list(rec(0, []))


class ChipMap:
    """Cluster-wide chip map: node -> local chip table.

    The TPU edition of the reference's `gpu-map` ConfigMap
    (controller.go:888-924): each node's value is lines of
    ``<index> <chip_id> <x,y[,z]> [topology]``. Parsed leniently; the
    topology token (first line) records the host slice shape. Two optional
    lines support multi-host slices (`parallel/multihost.py`):
    ``origin: x,y[,z]`` — the host's corner in the GLOBAL coordinates of
    its slice (absent = the zero corner); ``slice: <id>`` — which physical
    slice the host belongs to (hosts of different slices share origin
    coordinates but no ICI, so a gang must never span slice ids).
    """

    def __init__(self) -> None:
        self._hosts: Dict[str, HostTopology] = {}
        self._origins: Dict[str, Tuple[int, ...]] = {}
        self._slices: Dict[str, str] = {}

    @classmethod
    def parse(cls, data: Dict[str, str]) -> "ChipMap":
        cm = cls()
        for node, text in data.items():
            chips: List[ChipInfo] = []
            topo: Optional[SliceTopology] = None
            origin: Optional[Tuple[int, ...]] = None
            for line in text.strip().splitlines():
                parts = line.split()
                if not parts:
                    continue
                if parts[0] == "topology:":
                    topo = SliceTopology.parse(parts[1])
                    continue
                if parts[0] == "origin:":
                    origin = tuple(int(x) for x in parts[1].split(","))
                    continue
                if parts[0] == "slice:":
                    cm._slices[node] = parts[1]
                    continue
                idx = int(parts[0])
                cid = parts[1]
                coords: Tuple[int, ...] = ()
                if len(parts) > 2:
                    coords = tuple(int(x) for x in parts[2].split(","))
                chips.append(ChipInfo(chip_id=cid, index=idx, coords=coords))
            if topo is None:
                topo = SliceTopology.parse(str(max(1, len(chips))))
            cm._hosts[node] = HostTopology(topology=topo, chips=chips)
            if origin is not None:
                cm._origins[node] = origin
        return cm

    def dump(self) -> Dict[str, str]:
        out: Dict[str, str] = {}
        for node, host in self._hosts.items():
            lines = [f"topology: {host.topology}"]
            if node in self._origins:
                lines.append(
                    "origin: " + ",".join(str(x) for x in self._origins[node])
                )
            if node in self._slices:
                lines.append(f"slice: {self._slices[node]}")
            for c in sorted(host.chips, key=lambda c: c.index):
                coord = ",".join(str(x) for x in c.coords)
                lines.append(f"{c.index} {c.chip_id} {coord}")
            out[node] = "\n".join(lines)
        return out

    def origin(self, node: str) -> Tuple[int, ...]:
        """Host origin in global slice coords ((0,...) if unrecorded)."""
        host = self._hosts.get(node)
        o = self._origins.get(node)
        if o is not None:
            return o
        ndim = len(host.topology.dims) if host is not None else 2
        return (0,) * ndim

    def set_origin(self, node: str, origin: Tuple[int, ...]) -> None:
        self._origins[node] = tuple(origin)

    def slice_id(self, node: str) -> str:
        """Physical-slice identity ("" if unrecorded: clusters with a single
        multi-host slice can omit it)."""
        return self._slices.get(node, "")

    def set_slice_id(self, node: str, slice_id: str) -> None:
        self._slices[node] = slice_id

    def host(self, node: str) -> Optional[HostTopology]:
        return self._hosts.get(node)

    def set_host(self, node: str, host: HostTopology) -> None:
        self._hosts[node] = host

    def indices_for(self, node: str, chip_ids: Sequence[str]) -> List[int]:
        host = self._hosts.get(node)
        if host is None:
            raise KeyError(f"no chip map for node {node}")
        return host.indices_for(chip_ids)
