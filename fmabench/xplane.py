"""From a profiler trace (``.xplane.pb``) to numbers: the reduction every PR
shares, so that no PR that claims a gain computes its own.

Read with ``jax.profiler.ProfileData`` (nothing but JAX; importing it
starts no backend). What is taken:

* device planes ``/device:TPU:<n>``, their ``XLA Ops`` line: one event per
  executed op, start and duration in nanoseconds;
* busy seconds = the union of those intervals, averaged over the device
  planes; the window is the span from the first to the last event on any
  plane, host threads included (the traced stretch);
* idle gaps = the holes in that union, each named by what the host was
  doing: the program span (``GET /v1/traces``) or, failing that, the host
  plane event that covers most of the gap.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ops that only wrap other ops on the same line (their time is their
#: children's): counted in the busy union, left out of the top-ops list
WRAPPER_OPCODES = ("while", "conditional", "call")
#: host events that cover everything or say nothing about the work
_HOST_NOISE = re.compile(
    r"^(ThreadpoolListener|\$|ProfilerSession|Thread|process_|EventLoop)"
)


def _parse(full: str):
    """(name, result, opcode) of one HLO line as the trace names an op:
    ``%fusion.7 = bf16[8,4096]{...} fusion(...), kind=...``. A tuple result
    is skipped by its parentheses. ``None`` for any other kind of name."""
    name, sep, rest = full.partition(" = ")
    if not sep:
        return None
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = "tuple", rest[i + 1:].lstrip()
        m = re.match(r"^([a-z][a-z0-9\-]*)\(", rest)
    else:
        result = rest.split("{", 1)[0].split(" ", 1)[0]
        m = re.search(r"[}\]] ([a-z][a-z0-9\-]*)\(", rest)
    return name.lstrip("%"), result, (m.group(1) if m else "")


def short_name(full: str) -> str:
    """``fusion.7 fusion bf16[8,4096]`` for the HLO line above: a name a
    ledger can hold."""
    parsed = _parse(full)
    if parsed is None:
        return full[:120]
    name, result, op = parsed
    return f"{name} {op} {result}"[:120]


def opcode(full: str) -> str:
    parsed = _parse(full)
    return parsed[2] if parsed else ""


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return paths[-1] if paths else None


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


class TraceSummary:
    """Device-op intervals of one trace, reduced on demand."""

    def __init__(
        self,
        device_ops: Dict[str, List[Tuple[str, float, float]]],
        host_events: List[Tuple[str, float, float]],
        modules: Optional[Dict[str, List[Tuple[str, float, float]]]] = None,
    ) -> None:
        #: plane name -> [(op name, start_s, end_s)]
        self.device_ops = device_ops
        #: plane name -> [(program name, start_s, end_s)], one per execution
        self.modules = modules or {}
        #: [(event name, start_s, end_s)] on host threads, same clock
        self.host_events = host_events
        # the traced window: from the first to the last event the profiler
        # recorded on any plane, host threads included (they run all along)
        starts = [s for ops in device_ops.values() for _, s, _ in ops]
        ends = [e for ops in device_ops.values() for _, _, e in ops]
        starts += [s for _, s, _ in host_events]
        ends += [e for _, _, e in host_events]
        self.t0 = min(starts) if starts else 0.0
        self.t1 = max(ends) if ends else 0.0
        self.window_s = self.t1 - self.t0
        self._merged = {
            plane: merge([(s, e) for _, s, e in ops])
            for plane, ops in device_ops.items()
        }
        busy = [sum(hi - lo for lo, hi in m) for m in self._merged.values()]
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    def matching(
        self, regex: "re.Pattern[str]", line: str = "ops"
    ) -> Tuple[float, int]:
        """(device seconds, occurrences) of ops (``line="ops"``) or whole
        program executions (``line="modules"``) whose name matches,
        averaged over the device planes."""
        total, count = 0.0, 0
        rows = self.device_ops if line == "ops" else self.modules
        for ops in rows.values():
            for name, s, e in ops:
                if regex.search(name):
                    total += e - s
                    count += 1
        n = max(1, len(self.device_ops))
        return total / n, count // n

    def top_ops(self, k: int = 10) -> List[List[Any]]:
        acc: Dict[str, float] = {}
        for ops in self.device_ops.values():
            for name, s, e in ops:
                if opcode(name) not in WRAPPER_OPCODES:
                    acc[name] = acc.get(name, 0.0) + (e - s)
        n = max(1, len(self.device_ops))
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[short_name(name), sec / n] for name, sec in rows]

    def idle_gaps(
        self, k: int = 10, spans: Optional[List[Tuple[str, float, float]]] = None
    ) -> List[List[Any]]:
        """The k longest holes in the first device plane's busy union, each
        with the name of what the host was doing. ``spans`` are program
        spans already on this trace's clock."""
        if not self._merged:
            return []
        merged = next(iter(self._merged.values()))
        gaps = [
            (merged[i + 1][0] - merged[i][1], merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)
        ]
        gaps.sort(reverse=True)
        out = []
        for dur, lo, hi in gaps[:k]:
            out.append([self._name_gap(lo, hi, spans or []), dur])
        return out

    def _name_gap(
        self, lo: float, hi: float, spans: List[Tuple[str, float, float]]
    ) -> str:
        def best(events, prefix):
            # the event covering most of the gap; among equals the shortest,
            # which is the innermost
            top, top_key = None, None
            for name, s, e in events:
                cover = min(hi, e) - max(lo, s)
                if cover <= 0:
                    continue
                key = (round(cover / (hi - lo), 2), -(e - s))
                if top_key is None or key > top_key:
                    top, top_key = prefix + name, key
            return top, (top_key[0] if top_key else 0.0)

        host, host_cover = best(self.host_events, "host:")
        span, span_cover = best(spans, "span:")
        if host and span:
            return f"{span}|{host}"
        return span or host or "unattributed"


def summarize(path: str) -> Optional[TraceSummary]:
    """Reduce one ``.xplane.pb``; ``None`` when it holds no device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    host_events: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            ops = [
                (ev.name, ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9)
                for ln in lines for ev in ln.events
            ]
            if ops:
                device_ops[plane.name] = ops
            mods = [
                (ev.name, ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9)
                for ln in plane.lines if ln.name == MODULES_LINE
                for ev in ln.events
            ]
            if mods:
                modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.duration_ns < 1e6 or _HOST_NOISE.match(ev.name):
                        continue  # under a millisecond names no 0.3 s gap
                    host_events.append((
                        ev.name, ev.start_ns / 1e9,
                        (ev.start_ns + ev.duration_ns) / 1e9,
                    ))
    if not device_ops:
        return None
    return TraceSummary(device_ops, host_events, modules)
