"""The one rule for where compiled programs are cached on disk.

On TPU compile is the larger half of a cold start, and a wake after device
release re-lowers every program through the persistent XLA cache
(engine/device.py), so every entry point — engine server, launcher preload,
chip_smoke.py — arms the cache through :func:`arm` and no other
code names a cache directory:

  * ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and nothing else is
    set in code (JAX reads the variable itself);
  * unset: one fixed path inside the checkout (git-ignored). The path is
    part of how a deployment finds its entries again, so it is never a
    temporary name, a pid or a timestamp.

The variable is exported so children inherit the same directory, and
``FMA_EXEC_SPILL_DIR`` (engine/exec_pool.py) defaults to a sub-directory.
A process held to the CPU (``JAX_PLATFORMS=cpu``) arms nothing by itself:
XLA:CPU executables have reloaded from disk with different numerics, and
compile time there is noise.

The module also counts: how the cache fared (``requests`` / ``hits`` /
``writes``) and, by program (``by_program``, under the name jax gives the
compile, ``jit(chunk)``), the three things jax does before a program can
run (a serving program that the executable pool reloads skips the first
two, :func:`count_reload`): tracing it (``trace_s``), lowering the jaxpr to an MLIR module
(``lower_s``) and the backend compile or the cache load that stood in for
one (``n``, ``seconds``). jax times each with its own event; a thread's
events nest (every ``jnp`` function a program calls is traced inside the
program's trace, a constant folded eagerly compiles inside it), and a
second is counted once, under the kind of its innermost event and the
name of its outermost program, so the totals are the union of what the
thread spent. While tracing is enabled
each is also a span (``program.trace`` / ``program.lower`` /
``program.compile``). Once :func:`serving` has been called each further
compile is logged, so one that falls into serving time names itself.
"""

from __future__ import annotations

import collections
import logging
import os
import sys
import threading
from typing import Dict, List

from . import tracing

logger = logging.getLogger(__name__)

ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.xla-cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".xla-cache",
)

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
#: the three timed events of a program, by the kind this module calls them.
#: jax times ``compile_or_get_cached`` under the last: a backend compile
#: or, with the persistent cache armed, the load that stood in for one
_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: the part of a cache load that is reading and deserializing the entry
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_counts: "collections.Counter[str]" = collections.Counter()
#: program -> {"n": compiles and cache loads, "compile" / "trace" / "lower":
#: seconds}; guarded by _mu (the AOT warm-up compiles on its own threads)
_by_program: Dict[str, Dict[str, float]] = {}
_retrieval_s = 0.0
_mu = threading.Lock()
#: per thread: ``events``, the timed events not yet inside a later one, in
#: the order they ended, and ``hit``, a cache hit since the last compile
_thread = threading.local()
#: a trace holds one event for every jnp function the program calls
_EVENTS_KEPT = 4096
_listening = False
_serving = False


def held_to_cpu() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def listen() -> None:
    """Count this process's compiles from here on (idempotent). Needs no
    cache: a CPU-held process compiles too."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    _listening = True


def serving() -> None:
    """The process has reported ready: every compile from here on is one
    that a request waited for, and is logged by name."""
    global _serving
    _serving = True


def arm() -> str:
    """Arm the persistent cache for this process and its children; returns
    the directory ("" when a CPU-held process armed nothing)."""
    listen()
    directory = os.environ.get(ENV, "")
    if not directory:
        if held_to_cpu():
            return ""
        directory = os.environ[ENV] = DEFAULT_DIR
    os.makedirs(directory, exist_ok=True)
    os.environ.setdefault(
        "FMA_EXEC_SPILL_DIR", os.path.join(directory, "exec-pool")
    )
    # every program counts on the wake path, not only the slow compiles
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax

    # jax reads the variables at import; a process that imported it earlier
    # is pointed at the same values here
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
    )
    return directory


def _on_event(event: str, **_: object) -> None:
    name = _EVENTS.get(event)
    if name:
        _counts[name] += 1
    if name == "hits":
        _thread.hit = True  # read by the compile event that encloses it


def _on_duration(event: str, duration: float, **_: object) -> None:
    global _retrieval_s
    if event == _RETRIEVAL_EVENT:
        with _mu:
            _retrieval_s += duration


def program_name(fun_name: str) -> str:
    """One name a program: jax's three events call it ``chunk``,
    ``jit_chunk`` or ``jit(chunk)`` by event and version; the block keeps
    the last form."""
    if fun_name.endswith(")"):
        return fun_name
    if fun_name.startswith(("jit_", "pmap_")):
        api, _, rest = fun_name.partition("_")
        return f"{api}({rest})"
    return f"jit({fun_name})"


def _credit(name: str, kind: str, seconds: float) -> None:
    row = _by_program.setdefault(
        name, {"n": 0, "compile": 0.0, "trace": 0.0, "lower": 0.0}
    )
    row[kind] += seconds


def _on_time_span(
    event: str, start: float, end: float, **kw: object
) -> None:
    kind = _KINDS.get(event)
    if kind is None:
        return
    name = program_name(str(kw.get("fun_name") or "?"))
    dur = end - start
    #: [start, seconds, kind, program, trace and lower seconds held]: what
    #: an event holds is its own seconds and those of the events inside it,
    #: credited to its program until a later event turns out to hold it
    events: List[list] = _thread.__dict__.setdefault("events", [])
    trace_s = lower_s = inside = 0.0
    with _mu:
        # events end innermost first: whatever started inside this one is
        # in it, and its tracing and lowering were this program's
        while events and events[-1][0] >= start:
            _, c_dur, _, c_name, c_trace, c_lower = events.pop()
            inside += c_dur
            trace_s += c_trace
            lower_s += c_lower
            _credit(c_name, "trace", -c_trace)
            _credit(c_name, "lower", -c_lower)
        own = max(0.0, dur - inside)
        if kind == "trace":
            trace_s += own
        elif kind == "lower":
            lower_s += own
        else:
            _credit(name, "compile", own)
            _by_program[name]["n"] += 1
        _credit(name, "trace", trace_s)
        _credit(name, "lower", lower_s)
    before = {e[2]: e for e in events[-2:] if e[3] == name}
    events.append([start, dur, kind, name, trace_s, lower_s])
    del events[:-_EVENTS_KEPT]
    if kind == "trace":
        return  # its span and its log line come with the lowering's
    attrs: Dict[str, object] = {"program": name}
    if kind == "compile":
        attrs["cache_hit"] = bool(getattr(_thread, "hit", False))
        _thread.hit = False
    if tracing.enabled():
        traced = before.get("trace")
        if kind == "lower" and traced is not None:
            _record("program.trace", traced[0], traced[0] + traced[1], **attrs)
        _record(f"program.{kind}", start, end, **attrs)
    if _serving and kind == "compile":
        logger.info(
            "compiled %s in %.3f s (traced in %.3f s, lowered in %.3f s)",
            name, dur,
            before["trace"][4] if "trace" in before else 0.0,
            before["lower"][5] if "lower" in before else 0.0,
        )


def count_reload(fun_name: str, start: float, end: float) -> None:
    """A serving program was taken from the executable pool
    (engine/exec_pool.py) with nothing traced or lowered: to the account
    it is what a load from jax's own cache is, a request and a hit whose
    seconds (wall clock, `start` to `end`) stand under the program's
    ``seconds`` and so in ``backend_s``, and one of
    ``programs_reloaded``."""
    name = program_name(fun_name)
    with _mu:
        _counts["requests"] += 1
        _counts["hits"] += 1
        _counts["reloaded"] += 1
        _credit(name, "compile", end - start)
        _by_program[name]["n"] += 1
    if tracing.enabled():
        _record(
            "program.compile", start, end,
            program=name, cache_hit=True, reloaded=True,
        )


def _record(span: str, start: float, end: float, **attrs: object) -> None:
    tracing.record_span(
        span, tracing.mono_of_wall(start), tracing.mono_of_wall(end), **attrs
    )


def stats() -> Dict[str, object]:
    """Where this process caches and how its compiles fared: ``requests``
    went through the cache, ``hits`` were read from it, ``writes`` were
    compiled and stored; ``programs_reloaded`` of the hits were serving
    programs reloaded as executables with nothing traced
    (:func:`count_reload`); ``by_program`` has, by the name jax gives the
    program, its compiles and cache loads (``n``, ``seconds``) and the
    seconds it took to trace and to lower; ``trace_s`` / ``lower_s`` /
    ``backend_s`` are their sums, and ``retrieval_s`` the part of
    ``backend_s`` spent reading entries of the cache."""
    jax = sys.modules.get("jax")
    directory = jax.config.jax_compilation_cache_dir if jax else None
    with _mu:
        by_program = {
            name: {
                "n": int(row["n"]),
                "seconds": round(row["compile"], 6),
                "trace_s": round(row["trace"], 6),
                "lower_s": round(row["lower"], 6),
            }
            for name, row in sorted(_by_program.items())
            # a jnp function traced inside a program left its seconds there
            if row["n"] or row["trace"] >= 5e-7 or row["lower"] >= 5e-7
        }
        retrieval_s = _retrieval_s

    def total(key: str) -> float:
        return round(sum(row[key] for row in by_program.values()), 6)

    return {
        "dir": directory or "",
        "requests": _counts["requests"],
        "hits": _counts["hits"],
        "writes": _counts["writes"],
        "programs_reloaded": _counts["reloaded"],
        "trace_s": total("trace_s"),
        "lower_s": total("lower_s"),
        "backend_s": total("seconds"),
        "retrieval_s": round(retrieval_s, 6),
        "by_program": by_program,
    }
