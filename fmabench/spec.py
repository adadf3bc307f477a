"""Where the benchmark's data lives, found by the names in BENCHMARK.json.

A cell is one ``workloads`` entry: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix or one per-layer
metric is a file of its own (``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.json``), so a later PR adds
files and entries and edits nothing that is here. Standard library only:
the parent process of a run never initialises JAX.
"""

from __future__ import annotations

import json
import os
import re
import shlex
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
OUT_DIR = os.path.join(HERE, "out")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: HF ``config.json`` keys that are widths: never cut (model-configs §4)
WIDTH_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_per_tok", "vocab_size",
    "num_local_experts",
)


def load_json(path: str) -> Any:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def benchmark(path: str = BENCHMARK_JSON) -> Dict[str, Any]:
    return load_json(path)


def _by_name(rows: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for row in rows:
        if row["name"] == name:
            return row
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _data_file(kind: str, name: str, data_dir: str = "") -> str:
    """``<kind>/<name>.json`` under ``data_dir`` (a rehearsal's own files)
    if it is there, else under ``fmabench/``."""
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} has characters a name may not")
    if data_dir:
        own = os.path.join(ROOT, data_dir, kind, name + ".json")
        if os.path.exists(own):
            return own
    return os.path.join(HERE, kind, name + ".json")


class Cell:
    """One workload with its configuration, traffic and metric files."""

    def __init__(self, bench: Dict[str, Any], workload: str) -> None:
        self.bench = bench
        #: only a rehearsal's benchmark file has this key
        self.data_dir = bench.get("data_dir", "")
        self.workload = _by_name(bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg_row = _by_name(bench["configs"], self.workload["config"], "config")
        self.config_name = cfg_row["name"]
        self.config = load_json(os.path.join(ROOT, cfg_row["file"]))
        self.traffic_name = self.workload["traffic"]
        self.traffic = load_json(
            _data_file("traffic", self.traffic_name, self.data_dir)
        )

    # -- metrics ---------------------------------------------------------

    def _applies(self, metric: Dict[str, Any]) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        """The cell's per-layer metrics, each with its reader file merged
        in under ``reader``."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if not self._applies(m):
                continue
            if "workloads" not in m and m["moves"] not in e2e:
                continue
            out.append({**m, "reader": metric_file(m["name"], self.data_dir)})
        return out

    # -- the engine this cell serves with ---------------------------------

    def engine_options(self, traced: bool) -> List[str]:
        """The mix's server flags; a traced run also has the program keep
        every request's spans (the idle gaps are named by them)."""
        opts = shlex.split(self.traffic.get("engine_options", ""))
        return opts + ["--trace-requests", "1.0"] if traced else opts

    def engine_option(self, flag: str, default: Any = None) -> Any:
        opts = self.engine_options(False)
        if flag in opts:
            return opts[opts.index(flag) + 1]
        return default


def metric_file(name: str, data_dir: str = "") -> Dict[str, Any]:
    return load_json(_data_file("metrics", name, data_dir))


def config_file(path_or_name: str) -> Dict[str, Any]:
    """A configuration by file path (as BENCHMARK.json gives it, or any
    other path) or by name under ``configs/``."""
    if os.path.sep in path_or_name or path_or_name.endswith(".json"):
        path = path_or_name
        if not os.path.isabs(path):
            path = os.path.join(ROOT, path)
        return load_json(path)
    return load_json(_data_file("configs", path_or_name))


def peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in fmabench/peaks.json: an "
            "unknown device is an error, not a default"
        )
    return table["devices"][device_kind]


def model_dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs, from HF ``config.json`` keys."""
    heads = int(config["num_attention_heads"])
    hidden = int(config["hidden_size"])
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": hidden,
        "num_layers": int(config["num_hidden_layers"]),
        "num_heads": heads,
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or hidden // heads),
        "intermediate_size": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "num_experts": int(config.get("num_local_experts") or 0),
        "experts_per_token": int(config.get("num_experts_per_tok") or 0),
        "max_context": int(config["assumed"]["max_context"]),
    }


def param_count(d: Dict[str, Any]) -> int:
    h, f = d["hidden_size"], d["intermediate_size"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    attn = h * q + 2 * h * kv + q * h + 2 * h
    if d["num_experts"] > 1:
        ffn = h * d["num_experts"] + d["num_experts"] * 3 * h * f
    else:
        ffn = 3 * h * f
    return d["num_layers"] * (attn + ffn) + 2 * d["vocab_size"] * h + h
