"""ctypes binding to the C++ chip-telemetry shim (`native/tpuinfo/`).

The reference delegates accelerator identity/telemetry to NVML/`nvidia-smi`;
there is no TPU equivalent of "nvidia-smi for another process's HBM", so this
shim is authored natively (SURVEY.md §2.9, §7): chip enumeration from the PCI
tree / devfs and per-chip HBM usage where the runtime exposes it.

The shared library is looked up at $FMA_TPUINFO_LIB, next to this file, or in
the repo's native/build directory (`make -C native`). All entry points raise
RuntimeError when the shim isn't built.
"""

from __future__ import annotations

import ctypes
import json
import os
from typing import Dict, List, Optional

_LIB = None
_SEARCH = (
    os.environ.get("FMA_TPUINFO_LIB", ""),
    os.path.join(os.path.dirname(__file__), "libtpuinfo.so"),
    os.path.join(
        os.path.dirname(__file__), "..", "..", "native", "build", "libtpuinfo.so"
    ),
)


def _lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is None:
        for path in _SEARCH:
            if path and os.path.exists(path):
                lib = ctypes.CDLL(path)
                lib.tpuinfo_query.restype = ctypes.c_void_p
                lib.tpuinfo_query.argtypes = []
                lib.tpuinfo_free.restype = None
                lib.tpuinfo_free.argtypes = [ctypes.c_void_p]
                _LIB = lib
                break
        else:
            raise RuntimeError(
                "libtpuinfo.so not built: run `make -C native`"
            )
    return _LIB


def query() -> Dict:
    """The shim's whole document: ``chips``, ``topology`` and the ``source``
    the enumeration used ("pci+vfio", "pci", "devfs", "mock", "none")."""
    lib = _lib()
    ptr = lib.tpuinfo_query()
    if not ptr:
        raise RuntimeError("tpuinfo_query returned NULL")
    try:
        raw = ctypes.string_at(ptr)
    finally:
        lib.tpuinfo_free(ptr)
    return json.loads(raw.decode())


def enumerate_chips() -> List[Dict]:
    """[{chip_id, index, coords?, total_hbm_bytes?}] for local TPU chips."""
    return query().get("chips", [])


def host_topology() -> Optional[str]:
    return query().get("topology") or None


def hbm_usage() -> Dict[str, int]:
    """chip_id -> bytes of HBM in use (0 when the runtime hides it)."""
    return {
        c["chip_id"]: int(c.get("hbm_used_bytes", 0))
        for c in query().get("chips", [])
    }


def main(argv: Optional[List[str]] = None) -> int:
    """CLI for chip-map probe pods (`python -m ...native.tpuinfo --table`):
    prints the ChipMap line grammar the controller parses — the tpuinfo
    analogue of the reference probe pods' `nvidia-smi --query-gpu=index,uuid`
    (scripts/ensure-nodes-mapped.sh)."""
    import argparse

    p = argparse.ArgumentParser(prog="fma-tpuinfo")
    p.add_argument(
        "--table",
        action="store_true",
        help="chip-map grammar: 'topology: TxU' then '<index> <chip_id> <x,y>'",
    )
    args = p.parse_args(argv)
    if args.table:
        topo = host_topology()
        if topo:
            print(f"topology: {topo}")
        # Multi-host slice identity (parallel/multihost.py plans gangs from
        # these): FMA_HOST_ORIGIN/FMA_SLICE_ID override; else derive the
        # origin from the libtpu worker index (v5e multi-host slices tile
        # hosts along the first axis) and the slice id from TPU_NAME.
        origin = os.environ.get("FMA_HOST_ORIGIN", "")
        if not origin and topo:
            wid = os.environ.get("TPU_WORKER_ID", "")
            if wid.isdigit() and int(wid) > 0:
                dims = [int(d) for d in topo.split("x")]
                o = [0] * len(dims)
                o[0] = int(wid) * dims[0]
                origin = ",".join(str(x) for x in o)
        slice_id = os.environ.get(
            "FMA_SLICE_ID", os.environ.get("TPU_NAME", "")
        )
        if origin:
            print(f"origin: {origin}")
        if slice_id:
            print(f"slice: {slice_id}")
        for c in sorted(enumerate_chips(), key=lambda c: int(c["index"])):
            coords = ",".join(str(x) for x in (c.get("coords") or []))
            print(f"{c['index']} {c['chip_id']} {coords}".rstrip())
    else:
        print(json.dumps(query(), indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
