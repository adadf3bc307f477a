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
``writes``) and, by program, every backend compile or cache load this
process made (``by_program``: jax's ``fun_name``, e.g. ``jit(chunk)``). Once
:func:`serving` has been called each further one is logged, so a compile
that falls into serving time names itself.
"""

from __future__ import annotations

import collections
import logging
import os
import sys
from typing import Dict

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
#: jax times ``compile_or_get_cached`` under this event: a backend compile
#: or, with the persistent cache armed, the load that stood in for one
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counts: "collections.Counter[str]" = collections.Counter()
#: fun_name -> [compiles and cache loads, their seconds]
_by_program: Dict[str, list] = {}
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


def _on_duration(event: str, duration: float, **kw: object) -> None:
    if event != _COMPILE_EVENT:
        return
    name = str(kw.get("fun_name") or "?")
    row = _by_program.setdefault(name, [0, 0.0])
    row[0] += 1
    row[1] += duration
    if _serving:
        logger.info("compiled %s in %.3f s", name, duration)


def stats() -> Dict[str, object]:
    """Where this process caches and how its compiles fared: ``requests``
    went through the cache, ``hits`` were read from it, ``writes`` were
    compiled and stored; ``by_program`` has every compile or cache load
    by the name jax gives the program."""
    jax = sys.modules.get("jax")
    directory = jax.config.jax_compilation_cache_dir if jax else None
    return {
        "dir": directory or "",
        "requests": _counts["requests"],
        "hits": _counts["hits"],
        "writes": _counts["writes"],
        "by_program": {
            name: {"n": n, "seconds": round(sec, 6)}
            for name, (n, sec) in sorted(_by_program.items())
        },
    }
