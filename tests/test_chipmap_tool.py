"""chip-map population tool (the reference's ensure-nodes-mapped.sh for TPU:
gpu-map ConfigMap population, scripts/ensure-nodes-mapped.sh:1-66)."""

from llm_d_fast_model_actuation_tpu.api import constants as C
from llm_d_fast_model_actuation_tpu.controller.chipmap_tool import (
    ensure_nodes_mapped,
    tpu_nodes,
)
from llm_d_fast_model_actuation_tpu.controller.store import InMemoryStore
from llm_d_fast_model_actuation_tpu.parallel.topology import ChipMap, HostTopology

NS = "fma"


def _node(name, tpu=True, unschedulable=False, labels=None):
    return {
        "kind": "Node",
        "metadata": {"name": name, "labels": labels or {}},
        "spec": {"unschedulable": unschedulable} if unschedulable else {},
        "status": {"capacity": {"google.com/tpu": "4"} if tpu else {"cpu": "8"}},
    }


def _store(*nodes):
    s = InMemoryStore()
    for n in nodes:
        s.create(n)
    return s


def test_node_selection():
    s = _store(
        _node("tpu1"),
        _node("cpu1", tpu=False),
        _node("cordoned", unschedulable=True),
        _node("labeled", tpu=False, labels={"pool": "tpu"}),
    )
    assert [n["metadata"]["name"] for n in tpu_nodes(s)] == ["tpu1"]
    assert [n["metadata"]["name"] for n in tpu_nodes(s, {"pool": "tpu"})] == [
        "labeled"
    ]


def test_populates_missing_nodes_idempotently():
    s = _store(_node("n1"), _node("n2"))
    probed = []

    def prober(node):
        probed.append(node)
        return HostTopology.make("2x2", node=node)

    added = ensure_nodes_mapped(s, NS, prober)
    assert sorted(added) == ["n1", "n2"]
    cm = s.get("ConfigMap", NS, C.CHIP_MAP_CONFIGMAP)
    parsed = ChipMap.parse(cm["data"])
    host = parsed.host("n1")
    assert host is not None and len(host.chips) == 4
    assert str(host.topology) == "2x2"
    assert host.chips[0].coords == (0, 0)

    # second run: map is append-only, nothing re-probed
    probed.clear()
    assert ensure_nodes_mapped(s, NS, prober) == []
    assert probed == []


def test_existing_entries_preserved_and_failures_skipped():
    s = _store(_node("mapped"), _node("flaky"))
    s.create(
        {
            "kind": "ConfigMap",
            "metadata": {"name": C.CHIP_MAP_CONFIGMAP, "namespace": NS},
            "data": {"mapped": "topology: 1x1\n0 custom-id 0,0"},
        }
    )

    added = ensure_nodes_mapped(s, NS, lambda node: None)  # all probes fail
    assert added == []
    cm = s.get("ConfigMap", NS, C.CHIP_MAP_CONFIGMAP)
    assert cm["data"]["mapped"].startswith("topology: 1x1"), "kept verbatim"
    assert "flaky" not in cm["data"]

    # the flaky node recovers on a later run
    added = ensure_nodes_mapped(
        s, NS, lambda node: HostTopology.make("1x2", node=node)
    )
    assert added == ["flaky"]


def test_tpuinfo_table_cli_output_parses():
    """The probe pod's stdout (tpuinfo --table) round-trips through
    ChipMap.parse — the contract between the shim CLI and this tool."""
    import io
    import sys
    from unittest import mock

    from llm_d_fast_model_actuation_tpu.native import tpuinfo

    fake = {
        "topology": "2x2",
        "chips": [
            {"chip_id": f"tpu-local-{x}-{y}", "index": 2 * x + y,
             "coords": [x, y]}
            for x in range(2)
            for y in range(2)
        ],
    }
    buf = io.StringIO()
    with mock.patch.object(tpuinfo, "query", return_value=fake):
        with mock.patch.object(sys, "stdout", buf):
            tpuinfo.main(["--table"])
    parsed = ChipMap.parse({"local": buf.getvalue()})
    host = parsed.host("local")
    assert host is not None and len(host.chips) == 4
    assert host.by_id()["tpu-local-1-1"].coords == (1, 1)


def test_prober_chipmap_carries_multihost_identity():
    """A ChipMap-returning prober preserves origin:/slice: lines — the
    multi-host gang planner's input survives the probe round-trip."""
    from llm_d_fast_model_actuation_tpu.api import constants as C
    from llm_d_fast_model_actuation_tpu.controller.chipmap_tool import (
        ensure_nodes_mapped,
    )
    from llm_d_fast_model_actuation_tpu.controller.store import InMemoryStore
    from llm_d_fast_model_actuation_tpu.parallel.topology import (
        ChipMap,
        HostTopology,
    )

    store = InMemoryStore()
    store.create(
        {
            "kind": "Node",
            "metadata": {"name": "mh1"},
            "status": {"capacity": {"google.com/tpu": "8"}},
        }
    )

    def prober(node):
        cm = ChipMap()
        cm.set_host(node, HostTopology.make("2x4", node=node))
        cm.set_origin(node, (2, 0))
        cm.set_slice_id(node, "sliceA")
        return cm

    added = ensure_nodes_mapped(store, "ns1", prober)
    assert added == ["mh1"]
    data = store.get("ConfigMap", "ns1", C.CHIP_MAP_CONFIGMAP)["data"]
    parsed = ChipMap.parse(data)
    assert parsed.origin("mh1") == (2, 0)
    assert parsed.slice_id("mh1") == "sliceA"


def test_tpuinfo_table_emits_multihost_identity(monkeypatch, capsys):
    from llm_d_fast_model_actuation_tpu.native import tpuinfo

    monkeypatch.setattr(
        tpuinfo, "query",
        lambda: {
            "topology": "2x4",
            "chips": [
                {"chip_id": "c0", "index": 0, "coords": [0, 0]},
                {"chip_id": "c1", "index": 1, "coords": [0, 1]},
            ],
        },
    )
    monkeypatch.delenv("FMA_HOST_ORIGIN", raising=False)
    monkeypatch.delenv("FMA_SLICE_ID", raising=False)
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    monkeypatch.setenv("TPU_NAME", "my-slice")
    tpuinfo.main(["--table"])
    out = capsys.readouterr().out
    assert "topology: 2x4" in out
    assert "origin: 2,0" in out  # worker 1 of 2x4 hosts -> x offset 2
    assert "slice: my-slice" in out

    # explicit override wins
    monkeypatch.setenv("FMA_HOST_ORIGIN", "4,0")
    tpuinfo.main(["--table"])
    assert "origin: 4,0" in capsys.readouterr().out
