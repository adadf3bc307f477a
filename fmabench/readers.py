"""Per-layer metric readers: a small closed set of kinds, each metric a file.

``fmabench/metrics/<metric>.json`` holds ``{"kind": ..., <arguments>}``.
A reader gets the run's :class:`Evidence` and returns a number, or ``None``
when it finds nothing to read — the harness then leaves the metric out of
the line. A share of a roofline or of a peak is never reported as 0.

Kinds
-----
``stats``     a JSON path in ``GET /v1/stats``: ``at`` = ``open`` | ``close``
              | ``delta`` (close - open) | ``ratio_open`` (``path`` over
              ``over`` at window open, as a percentage).
``prom``      a gauge in ``GET /metrics`` sampled through the window:
              mean of the samples, times the engine option named by
              ``scale_by_option`` where that is given.
``span``      spans named ``name`` in ``GET /v1/traces`` that start inside
              the window: ``stat`` = ``sum_s`` | ``count``.
``client``    a client-side series (``series``): ``stat`` = ``mean`` |
              ``sum`` | ``p<q>``.
``trace_op``  device ops (``line`` = ``ops``, the default) or whole program
              executions (``line`` = ``modules``) in the profiler trace whose
              name matches ``regex``: ``stat`` = ``sum_ms``
              | ``per_step_ms`` (per call and per step of the engine option
              ``steps_option``, or ``steps_default`` where the option is not
              given) | ``idle_pct`` (regex unused).
``roofline``  ``regex`` picks a kernel's events; ``function`` names a
              function in ``fmabench/roofline.py``, or else a file
              ``fmabench/rooflines/<function>.py`` that defines it, which
              gives the bytes and flops the algorithm needs per call from
              the cell's shapes (its family's sizes and the live contexts);
              the least time at the table's peaks over the measured time,
              as a percentage.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional

from . import roofline as roofline_mod
from . import spec
from .traffic import percentile


class Evidence:
    """What one run gathered for the readers. Every field may be empty."""

    def __init__(self) -> None:
        self.stats_open: Dict[str, Any] = {}
        self.stats_close: Dict[str, Any] = {}
        #: gauge name -> samples through the window
        self.prom: Dict[str, List[float]] = {}
        #: chrome trace events of GET /v1/traces
        self.spans: List[Dict[str, Any]] = []
        #: wall-clock (unix seconds) bounds of the window
        self.wall_open = 0.0
        self.wall_close = 0.0
        #: series name -> values, client clock
        self.series: Dict[str, List[float]] = {}
        #: fmabench.xplane.TraceSummary or None
        self.trace: Any = None
        #: sizes for the roofline functions
        self.shapes: Dict[str, Any] = {}
        self.peaks: Dict[str, Any] = {}
        self.engine_option: Callable[[str, Any], Any] = lambda flag, d=None: d
        #: a rehearsal's own data files, looked in first (roofline files)
        self.data_dir = ""


def dig(doc: Any, path: str) -> Any:
    for key in path.split("."):
        if isinstance(doc, list):
            doc = doc[int(key)]
        elif isinstance(doc, dict) and key in doc:
            doc = doc[key]
        else:
            return None
    return doc


def _num(x: Any) -> Optional[float]:
    return float(x) if isinstance(x, (int, float)) and not isinstance(x, bool) else None


def read_stats(r: Dict[str, Any], ev: Evidence) -> Optional[float]:
    at = r.get("at", "close")
    a, b = _num(dig(ev.stats_open, r["path"])), _num(dig(ev.stats_close, r["path"]))
    if at == "open":
        return a
    if at == "close":
        return b
    if at == "delta":
        return None if a is None or b is None else b - a
    if at == "ratio_open":
        over = _num(dig(ev.stats_open, r["over"]))
        if a is None or not over:
            return None
        return 100.0 * a / over
    raise ValueError(f"stats reader: unknown at={at!r}")


def read_prom(r: Dict[str, Any], ev: Evidence) -> Optional[float]:
    samples = ev.prom.get(r["name"]) or []
    if not samples:
        return None
    mean = sum(samples) / len(samples)
    if "scale_by_option" in r:
        mean *= float(ev.engine_option(r["scale_by_option"], 1))
    return mean


def _stat(values: List[float], stat: str) -> Optional[float]:
    if not values:
        return None
    if stat == "sum":
        return sum(values)
    if stat == "mean":
        return sum(values) / len(values)
    if stat.startswith("p"):
        return percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")


def window_spans(ev: Evidence, name: str) -> List[Dict[str, Any]]:
    lo, hi = ev.wall_open * 1e6, ev.wall_close * 1e6
    return [
        e for e in ev.spans
        if e.get("name") == name and e.get("ph") == "X" and lo <= e["ts"] < hi
    ]


def read_span(r: Dict[str, Any], ev: Evidence) -> Optional[float]:
    spans = window_spans(ev, r["name"])
    if not spans:
        return None
    stat = r.get("stat", "sum_s")
    if stat == "sum_s":
        return sum(e["dur"] for e in spans) / 1e6
    if stat == "count":
        return float(len(spans))
    raise ValueError(f"span reader: unknown stat {stat!r}")


def read_client(r: Dict[str, Any], ev: Evidence) -> Optional[float]:
    return _stat(list(ev.series.get(r["series"]) or []), r["stat"])


def read_trace_op(r: Dict[str, Any], ev: Evidence) -> Optional[float]:
    tr = ev.trace
    if tr is None or tr.busy_s <= 0:
        return None
    stat = r["stat"]
    if stat == "idle_pct":
        return 100.0 * (1.0 - tr.busy_s / tr.window_s)
    total_s, count = tr.matching(re.compile(r["regex"]), r.get("line", "ops"))
    if count == 0:
        return None
    if stat == "sum_ms":
        return total_s * 1e3
    if stat == "per_step_ms":
        steps = float(
            ev.engine_option(r["steps_option"], r.get("steps_default", 1))
        )
        return total_s * 1e3 / (count * steps)
    raise ValueError(f"trace_op reader: unknown stat {stat!r}")


def read_roofline(r: Dict[str, Any], ev: Evidence) -> Optional[float]:
    tr = ev.trace
    if tr is None:
        return None
    total_s, calls = tr.matching(re.compile(r["regex"]))
    if calls == 0 or total_s <= 0:
        return None
    fn = getattr(roofline_mod, r["function"], None) or spec.roofline_function(
        r["function"], ev.data_dir
    )
    need = fn(ev.shapes)
    if need is None:
        return None
    least_s = max(
        need["flops"] / ev.peaks["bf16_flops_per_s"],
        need["bytes"] / ev.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (total_s / calls)


KINDS: Dict[str, Callable[[Dict[str, Any], Evidence], Optional[float]]] = {
    "stats": read_stats, "prom": read_prom, "span": read_span, "client": read_client, "trace_op": read_trace_op,
    "roofline": read_roofline,
}


def read_metric(reader: Dict[str, Any], ev: Evidence) -> Optional[float]:
    kind = reader["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown reader kind {kind!r}; known: {sorted(KINDS)}")
    return KINDS[kind](reader, ev)
