"""Native tpuinfo shim: build, enumeration sources, cooperative HBM usage.

The C++ shim (native/tpuinfo/tpuinfo.cpp) is the TPU build's replacement for
the reference's NVML/`nvidia-smi` telemetry path
(pkg/server/requester/coordination/server.go:55,100). These tests build it
with the in-tree Makefile and exercise every enumeration source through the
real ctypes binding — no TPU hardware involved.
"""

import json
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "native", "build", "libtpuinfo.so")


@pytest.fixture(scope="session")
def shim_lib():
    subprocess.run(["make", "-C", os.path.join(REPO, "native")], check=True)
    assert os.path.exists(LIB)
    return LIB


@pytest.fixture()
def tpuinfo(shim_lib, monkeypatch):
    monkeypatch.setenv("FMA_TPUINFO_LIB", shim_lib)
    from llm_d_fast_model_actuation_tpu.native import tpuinfo as mod

    # The binding caches the CDLL; fine — env vars are read per query.
    return mod


def test_mock_count_enumeration(tpuinfo, monkeypatch):
    monkeypatch.setenv("FMA_TPUINFO_MOCK_COUNT", "8")
    chips = tpuinfo.enumerate_chips()
    assert [c["index"] for c in chips] == list(range(8))
    assert chips[0]["chip_id"] == "mock-chip-0"
    assert chips[0]["total_hbm_bytes"] == 16 << 30
    assert tpuinfo.host_topology() == "2x4"
    # Coords must agree with the Python topology model exactly (placement
    # compares these tuples against HostTopology grid cells).
    from llm_d_fast_model_actuation_tpu.parallel.topology import HostTopology

    model = HostTopology.make("2x4", node="x")
    assert [tuple(c["coords"]) for c in chips] == [
        c.coords for c in model.chips
    ]


def test_mock_json_passthrough(tpuinfo, monkeypatch):
    doc = {"chips": [{"chip_id": "x", "index": 0}], "topology": "1x1"}
    monkeypatch.setenv("FMA_TPUINFO_MOCK_JSON", json.dumps(doc))
    assert tpuinfo.enumerate_chips() == doc["chips"]
    assert tpuinfo.host_topology() == "1x1"


def test_topology_override(tpuinfo, monkeypatch):
    monkeypatch.setenv("FMA_TPUINFO_MOCK_COUNT", "4")
    monkeypatch.setenv("FMA_TPUINFO_TOPOLOGY", "1x4")
    assert tpuinfo.host_topology() == "1x4"


def test_devfs_enumeration(tpuinfo, monkeypatch, tmp_path):
    for i in (0, 1, 2, 3, 10):  # accel10 sorts numerically, not lexically
        (tmp_path / f"accel{i}").touch()
    (tmp_path / "accelerometer").touch()  # not a chip node
    monkeypatch.setenv("FMA_TPUINFO_DEV_ROOT", str(tmp_path))
    # force past the pci source by pointing sysfs at an empty dir
    empty = tmp_path / "nopci"
    empty.mkdir()
    monkeypatch.setenv("FMA_TPUINFO_SYSFS_ROOT", str(empty))
    chips = tpuinfo.enumerate_chips()
    assert [c["chip_id"] for c in chips] == [
        "tpu-accel-0",
        "tpu-accel-1",
        "tpu-accel-2",
        "tpu-accel-3",
        "tpu-accel-10",
    ]


def test_pci_enumeration(tpuinfo, monkeypatch, tmp_path):
    def mkdev(addr, vendor, device):
        d = tmp_path / addr
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")

    mkdev("0000:00:01.0", "0x1ae0", "0x0063")  # v5e
    mkdev("0000:00:02.0", "0x1ae0", "0x005e")  # v4
    mkdev("0000:00:03.0", "0x10de", "0x2330")  # some GPU: ignored
    monkeypatch.setenv("FMA_TPUINFO_SYSFS_ROOT", str(tmp_path))
    chips = tpuinfo.enumerate_chips()
    assert len(chips) == 2
    by_id = {c["chip_id"]: c for c in chips}
    assert by_id["tpu-v5e-0000:00:01.0"]["total_hbm_bytes"] == 16 << 30
    assert by_id["tpu-v4-0000:00:02.0"]["total_hbm_bytes"] == 32 << 30
    assert by_id["tpu-v5e-0000:00:01.0"]["pci_addr"] == "0000:00:01.0"


def test_pci_enumeration_keeps_only_openable_vfio_groups(
    tpuinfo, monkeypatch, tmp_path
):
    """A Cloud TPU v5e host as a container sees it (checked on the chip
    machine, PR 21): sysfs lists all four chips, /dev/vfio holds the group
    of the one this container may open — and no /dev/accel*."""
    sysfs, dev = tmp_path / "sys", tmp_path / "dev"
    groups = tmp_path / "iommu_groups"
    for i, addr in enumerate(["0000:00:08.0", "0000:00:09.0", "0000:00:0a.0"]):
        d = sysfs / addr
        d.mkdir(parents=True)
        (d / "vendor").write_text("0x1ae0\n")
        (d / "device").write_text("0x0063\n")
        (groups / str(i)).mkdir(parents=True)
        (d / "iommu_group").symlink_to(groups / str(i))
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()  # the container node, not a group
    (dev / "vfio" / "1").touch()
    monkeypatch.setenv("FMA_TPUINFO_SYSFS_ROOT", str(sysfs))
    monkeypatch.setenv("FMA_TPUINFO_DEV_ROOT", str(dev))
    doc = tpuinfo.query()
    assert doc["source"] == "pci+vfio"
    assert [(c["chip_id"], c["index"]) for c in doc["chips"]] == [
        ("tpu-v5e-0000:00:09.0", 0)
    ]
    assert doc["topology"] == "1"


def test_cooperative_hbm_usage(tpuinfo, monkeypatch, tmp_path):
    """Publisher writes per-pid files; shim sums live writers, prunes dead."""
    from llm_d_fast_model_actuation_tpu.native.hbm_publisher import (
        HbmUsagePublisher,
    )

    monkeypatch.setenv("FMA_TPUINFO_MOCK_COUNT", "2")
    monkeypatch.setenv("FMA_TPUINFO_USAGE_DIR", str(tmp_path))

    pub = HbmUsagePublisher(["mock-chip-0", "mock-chip-1"], root=str(tmp_path))
    pub.set_uniform(2 << 30)
    usage = tpuinfo.hbm_usage()
    assert usage["mock-chip-0"] == 1 << 30
    assert usage["mock-chip-1"] == 1 << 30

    # A dead writer's file is pruned from the sum (and from disk).
    dead = tmp_path / "mock-chip-0" / "999999999"
    dead.write_text(str(8 << 30))
    assert tpuinfo.hbm_usage()["mock-chip-0"] == 1 << 30
    assert not dead.exists()

    # Sleep edge: publisher reports zero without removing its files.
    pub.set_uniform(0)
    assert tpuinfo.hbm_usage()["mock-chip-0"] == 0

    pub.clear()
    assert not (tmp_path / "mock-chip-0" / str(os.getpid())).exists()


def test_engine_service_publishes_usage(monkeypatch, tmp_path):
    """EngineService publishes live bytes, zero on sleep, live again on wake."""
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    monkeypatch.setenv("FMA_CHIP_IDS", "chipA,chipB")
    monkeypatch.setenv("FMA_TPUINFO_USAGE_DIR", str(tmp_path))
    svc = EngineService(parse_engine_options("--model tiny"))
    try:
        pid = str(os.getpid())
        a = int((tmp_path / "chipA" / pid).read_text())
        b = int((tmp_path / "chipB" / pid).read_text())
        assert a > 0 and abs(a - b) <= 1

        svc.sleep(1)
        assert int((tmp_path / "chipA" / pid).read_text()) == 0
        svc.wake_up()
        assert int((tmp_path / "chipA" / pid).read_text()) == a
    finally:
        svc.shutdown()
    assert not (tmp_path / "chipA" / pid).exists()
