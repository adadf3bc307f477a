"""Where the benchmark's data lives, found by the names in BENCHMARK.json.

A cell is one ``workloads`` entry: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix, one per-layer
metric or one model family is a file of its own (``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.json``, ``rooflines/<fn>.py``,
``families/<family>/{keys,program,reference}.py``), so a later PR adds
files and entries and edits nothing that is here. Standard library only:
the parent process of a run never initialises JAX (a family's ``keys.py``
is standard library too; its other two files load only in the children).
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shlex
from types import ModuleType
from typing import Any, Dict, List, Set

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
OUT_DIR = os.path.join(HERE, "out")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: the keys of a configuration file that are the harness's own documentation
#: and not the model's; every other key belongs to the file's family
DOC_KEYS = (
    "source", "published", "reduced", "assumed", "deployment", "check", "family",
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


def _data_file(kind: str, name: str, data_dir: str = "", ext: str = ".json") -> str:
    """``<kind>/<name><ext>`` under ``data_dir`` (a rehearsal's own files)
    if it is there, else under ``fmabench/``. With ``ext`` empty it is a
    directory, taken whole from the one place or the other."""
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} has characters a name may not")
    if data_dir:
        own = os.path.join(ROOT, data_dir, kind, name + ext)
        if os.path.exists(own):
            return own
    return os.path.join(HERE, kind, name + ext)


_MODULES: Dict[str, ModuleType] = {}


def load_py(path: str) -> ModuleType:
    """A Python data file (a family's part, a roofline function) by path:
    found by a name in a JSON file, so it is no module of the package and
    a later PR adds one without an edit here."""
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"the benchmark has no file {path}")
        tag = re.sub(r"\W", "_", os.path.relpath(path, ROOT)[:-3])
        mod_spec = importlib.util.spec_from_file_location("fmabench_file_" + tag, path)
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


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
        #: a file the family cannot read whole is refused here, before any
        #: child starts
        self.family = family_of(self.config, self.data_dir)
        self.dims = self.family.dims(self.config)

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


def roofline_function(function: str, data_dir: str = "") -> Any:
    """``<function>(shapes)`` as the file ``rooflines/<function>.py`` defines it."""
    module = load_py(_data_file("rooflines", function, data_dir, ext=".py"))
    return getattr(module, function)


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


# -- families: everything that belongs to one architecture -------------------------


def family_name(config: Dict[str, Any]) -> str:
    """The family a configuration file names. The two files accepted before
    families had names carry no ``family`` key and keep the rule they were
    read by then; every later file names its family."""
    if "family" in config:
        return str(config["family"])
    return "mixtral" if int(config.get("num_local_experts") or 0) > 1 else "mistral"


class _Tracked(dict):
    """A configuration that notes which of its keys were looked at."""

    def __init__(self, config: Dict[str, Any]) -> None:
        super().__init__(config)
        self.seen: Set[str] = set()

    def __getitem__(self, key: str) -> Any:
        self.seen.add(key)
        return super().__getitem__(key)

    def get(self, key: str, default: Any = None) -> Any:
        self.seen.add(key)
        return super().get(key, default)

    def __contains__(self, key: object) -> bool:
        self.seen.add(str(key))
        return super().__contains__(key)


class Family:
    """One architecture: the directory ``families/<name>/`` (under a
    rehearsal's ``data_dir`` first, taken whole from there), three files:

    ``keys.py``       standard library only. ``carried``: keys copied from
                      the source for the record, which change nothing that
                      is served; ``reducible``: the keys that may stand in
                      ``reduced`` (depth, the experts or the vocabulary
                      held here), every other being a width;
                      ``dims(config)``: the sizes, with ``vocab_size`` and
                      ``max_context`` for the harness and whatever the
                      family's other files need; ``param_count(dims)``;
                      ``kv_bytes(dims, num_pages, page_size)``.
    ``program.py``    ``build(dims)``: the program's config object. Imported
                      in the engine child and by ``rehearse.py`` only.
    ``reference.py``  ``init_weights(seed, dims)``, ``forward_logits(dims,
                      weights, ids, length, rows)``, ``MATMUL_WEIGHTS``.
                      Imported in the reference child only; imports nothing
                      of the program.

    A family that builds on another takes its parts from the directory
    beside its own (``sibling_part``), so the two are always found in the
    same place.
    """

    def __init__(self, name: str, data_dir: str = "") -> None:
        self.name = name
        self.dir = _data_file("families", name, data_dir, ext="")
        if not os.path.isdir(self.dir):
            raise KeyError(f"the benchmark has no model family {name!r} ({self.dir})")

    def part(self, which: str) -> ModuleType:
        return load_py(os.path.join(self.dir, which + ".py"))

    @property
    def keys(self) -> ModuleType:
        return self.part("keys")

    def dims(self, config: Dict[str, Any]) -> Dict[str, Any]:
        """The family's sizes for a configuration file, or an error that
        names what the file and the family do not agree on: a key that
        ``dims`` never looked at, or a cut of something that may not be cut."""
        keys = self.keys
        tracked = _Tracked(config)

        def unseen() -> List[str]:
            return sorted(
                set(config) - set(DOC_KEYS) - set(keys.carried) - tracked.seen
            )

        try:
            dims = keys.dims(tracked)
        except (KeyError, ValueError) as err:
            raise ValueError(
                f"family {self.name!r} cannot read this file "
                f"({type(err).__name__}: {err}); the keys it had not looked "
                f"at by then: {unseen()}"
            ) from err
        unknown = unseen()
        if unknown:
            raise ValueError(
                f"family {self.name!r} reads no configuration key {unknown}: a "
                "key dropped in silence would serve another model under this "
                'one\'s name (the file of another family says so: "family")'
            )
        for key in config.get("reduced") or []:
            if key not in keys.reducible or key.endswith(("_dim", "_rank")):
                raise ValueError(
                    f"family {self.name!r}: {key!r} may not stand in `reduced` "
                    f"(reducible: {sorted(keys.reducible)})"
                )
        for need in ("vocab_size", "max_context"):
            if need not in dims:
                raise KeyError(f"family {self.name!r}: dims() gives no {need}")
        return dims


def sibling_part(file: str, family: str, which: str) -> ModuleType:
    """For a family's file (``__file__``): the part ``which`` of the family
    in the directory beside its own."""
    there = os.path.dirname(os.path.dirname(os.path.abspath(file)))
    return load_py(os.path.join(there, family, which + ".py"))


def family_of(config: Dict[str, Any], data_dir: str = "") -> Family:
    return Family(family_name(config), data_dir)


def model_dims(config: Dict[str, Any], data_dir: str = "") -> Dict[str, Any]:
    """A configuration's sizes, by its family."""
    return family_of(config, data_dir).dims(config)
