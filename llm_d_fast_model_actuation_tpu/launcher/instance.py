"""One engine instance: a forked child process running the engine server.

Mirrors the reference's `VllmInstance` semantics (launcher.py:157-340):
status vocabulary (started / already_running / running / stopped /
not_running / terminated), per-instance log file dup2'd over the child's
stdout/stderr, graceful SIGTERM then process-group SIGKILL, and **sentinel
crash detection**: the child's `multiprocessing` sentinel fd is registered on
the event loop, so process death becomes a callback with zero polling.

TPU deltas: chip IDs translate to TPU_VISIBLE_DEVICES / process-bounds env
(not CUDA_VISIBLE_DEVICES), and the fork inherits the preloaded JAX modules
plus a shared persistent XLA compilation-cache dir (cold-start killer on TPU,
where compilation dominates).
"""

from __future__ import annotations

import logging
import multiprocessing
import os
import shlex
import signal
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from .chiptranslator import ChipTranslator

logger = logging.getLogger(__name__)

MAX_LOG_RESPONSE_BYTES = 1 << 20  # 1 MiB per ranged-log response

#: serializes the FMA_TRACEPARENT stamp -> fork -> restore window in
#: start(): os.environ is process-global, and concurrent creates (REST
#: handlers run manager verbs on the executor pool) would otherwise
#: cross-wire children's trace parents or leave a stale one behind
_FORK_ENV_LOCK = threading.Lock()


def replace_model_option(
    options: str, model: str, checkpoint_dir: str = ""
) -> str:
    """Rewrite the ``--model`` (and ``--checkpoint-dir``) values in an
    engine options string. After a hot-swap the child serves a different
    model than it was forked with; the stored config must describe reality
    (status responses, and any future restart of the instance) — which
    means the OLD model's checkpoint dir must never survive attached to
    the new model's name (a restart would load shape-mismatched weights)."""
    parts = shlex.split(options or "")
    out: List[str] = []
    replaced = False
    i = 0
    while i < len(parts):
        p = parts[i]
        if p == "--model" and i + 1 < len(parts):
            out += ["--model", model]
            i += 2
            replaced = True
        elif p.startswith("--model="):
            out.append(f"--model={model}")
            i += 1
            replaced = True
        elif p == "--checkpoint-dir" and i + 1 < len(parts):
            i += 2  # dropped; re-added below if the swap supplied one
        elif p.startswith("--checkpoint-dir="):
            i += 1
        else:
            out.append(p)
            i += 1
    if not replaced:
        out = ["--model", model] + out
    if checkpoint_dir:
        out += ["--checkpoint-dir", checkpoint_dir]
    return shlex.join(out)


class InvalidInstanceConfig(Exception):
    """The instance config is semantically invalid (e.g. unknown chip ID)."""


class HalfMade(Exception):
    """Something other than start() was the first op on an instance."""

    def __init__(self, instance_id: str) -> None:
        super().__init__(instance_id)
        self.instance_id = instance_id


class LogRangeNotAvailable(Exception):
    def __init__(self, requested: int, total: int) -> None:
        super().__init__(f"start {requested} beyond total {total}")
        self.requested = requested
        self.total = total


@dataclass
class InstanceConfig:
    """Wire config of one instance (reference VllmConfig, launcher.py:64-68).

    Serialized with the reference's field names (`options`, `gpu_uuids`,
    `env_vars`, `annotations`) so the reference's Go launcher client talks to
    this launcher unchanged; `chip_ids` is accepted as an input alias."""

    options: str = ""
    chip_ids: Optional[List[str]] = None
    env_vars: Optional[Dict[str, str]] = None
    annotations: Optional[Dict[str, str]] = None

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"options": self.options}
        if self.chip_ids is not None:
            d["gpu_uuids"] = list(self.chip_ids)
        if self.env_vars is not None:
            d["env_vars"] = dict(self.env_vars)
        if self.annotations is not None:
            d["annotations"] = dict(self.annotations)
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "InstanceConfig":
        if "options" not in d:
            raise ValueError("instance config requires 'options'")
        chips = d.get("chip_ids", d.get("gpu_uuids"))
        return cls(
            options=str(d["options"]),
            chip_ids=None if chips is None else [str(c) for c in chips],
            env_vars=None if d.get("env_vars") is None else dict(d["env_vars"]),
            annotations=None
            if d.get("annotations") is None
            else dict(d["annotations"]),
        )


def _close_inherited_sockets() -> None:
    """Close inherited *socket* fds in the child (keep pipes, incl. the
    sentinel) — the reference's fix for wedged client connections inherited
    across fork (launcher.py:808-832, issue #550)."""
    import stat

    for fd in range(3, 1024):
        try:
            mode = os.fstat(fd).st_mode
        except OSError:
            continue
        if stat.S_ISSOCK(mode):
            try:
                os.close(fd)
            except OSError:
                pass


def _apply_jax_env(env_vars: Dict[str, str]) -> None:
    """Point the already-imported jax at the instance's ``JAX_*`` variables.
    jax read its environment when the launcher imported it, before the fork:
    without this a child told ``JAX_PLATFORMS=cpu`` would still take the
    chip. (libtpu reads the TPU_* pinning variables when the child creates
    its client, which the launcher never does, so those need nothing.)"""
    import jax

    for key, raw in env_vars.items():
        name = key.lower()
        if not key.startswith("JAX_") or name not in jax.config.values:
            continue
        current = jax.config.values[name]
        value: Any = str(raw)
        if isinstance(current, bool):
            value = value.lower() in ("1", "true", "yes", "on")
        elif isinstance(current, (int, float)):
            value = type(current)(value)
        jax.config.update(name, value)


def engine_kickoff(config: InstanceConfig, log_path: str) -> None:
    """Child-process body: new process group, stdio -> log file, env, then
    the engine server (modules already imported pre-fork = preloading)."""
    os.setpgrp()
    _close_inherited_sockets()
    fd = os.open(log_path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
    os.dup2(fd, 1)
    os.dup2(fd, 2)
    if fd > 2:
        os.close(fd)
    for k, v in (config.env_vars or {}).items():
        os.environ[k] = str(v)
    _apply_jax_env(config.env_vars or {})
    # per-instance FMA_FAULTS must win over (latched) launcher-level state
    from ..utils import faults, tracing

    faults.load_env(force=True)
    # forked-child hygiene: drop the ring-buffer copy inherited from the
    # launcher and re-read FMA_TRACING/FMA_TRACE_BUFFER (per-instance
    # env_vars win); FMA_TRACEPARENT stays for the engine.start span
    tracing.reset_after_fork()
    # same hygiene for prometheus: the fork duplicated the launcher's
    # registered fma_launcher_rpc_seconds (frozen at fork time) into this
    # child's default registry — without this the engine's GET /metrics
    # would export stale launcher-family samples (docs/metrics.md pins
    # the family to the launcher port)
    try:
        from prometheus_client import REGISTRY

        from .manager import LAUNCHER_RPC_SECONDS

        REGISTRY.unregister(LAUNCHER_RPC_SECONDS)
    except (ImportError, KeyError):
        pass
    from ..engine.server import parse_engine_options, run_server

    args = parse_engine_options(config.options)
    run_server(args)


class EngineInstance:
    def __init__(
        self,
        instance_id: str,
        config: InstanceConfig,
        translator: ChipTranslator,
        log_dir: str = "",
        kickoff=engine_kickoff,
    ) -> None:
        # Translate chip IDs to device-pinning env at construction time
        # (the reference's CUDA_VISIBLE_DEVICES injection, launcher.py:175-191).
        if config.chip_ids:
            try:
                env = translator.env_for(config.chip_ids)
            except KeyError as e:
                raise InvalidInstanceConfig(f"unknown chip id {e.args[0]!r}")
            config.env_vars = {**(config.env_vars or {}), **env}
            logger.info(
                "instance %s: chips %s -> %s",
                instance_id,
                config.chip_ids,
                env["TPU_VISIBLE_DEVICES"],
            )
        self.instance_id = instance_id
        self.config = config
        self.process: Optional[multiprocessing.Process] = None
        self.last_revision: Optional[int] = None
        self._kickoff = kickoff
        self._sentinel_active = False
        self._on_exit_callback = None
        self._log_file_path = os.path.join(
            log_dir or "/tmp", f"launcher-{os.getpid()}-engine-{instance_id}.log"
        )

    # -- state rendering -----------------------------------------------------

    def _make_state(self, status: str) -> Dict[str, Any]:
        return {
            "status": status,
            "instance_id": self.instance_id,
            "revision": self.last_revision,
            # the child's pid (None pre-start): fault drills and the
            # supervisor e2e need a real process to signal
            "pid": self.process.pid if self.process is not None else None,
            **self.config.to_dict(),
        }

    # -- lifecycle -----------------------------------------------------------

    def start(
        self, fresh_log: bool = True, restart: bool = False
    ) -> Dict[str, Any]:
        if self.process and self.process.is_alive():
            return self._make_state("already_running")
        if fresh_log or not os.path.exists(self._log_file_path):
            open(self._log_file_path, "wb").close()
        else:
            # supervised restart: append below the crash forensics (the
            # kickoff opens O_APPEND), with a marker separating the lives
            with open(self._log_file_path, "ab") as f:
                f.write(b"\n--- supervised restart ---\n")
        self.process = multiprocessing.get_context("fork").Process(
            target=self._kickoff, args=(self.config, self._log_file_path)
        )
        # Cross-fork trace propagation: stamp the caller's span context
        # (the launcher's create/restart span) into the env the fork
        # inherits, so the child's engine.start span joins the trace
        # (utils/tracing.py; restored right after the fork — the env of a
        # long-lived launcher must not carry a stale parent). A
        # supervised restart additionally stamps FMA_RESTARTED so the
        # child's flight recorder (utils/costs.py) attributes its initial
        # cold build to restart churn, not client-driven actuation.
        from ..utils import tracing

        tp = tracing.current_traceparent()
        with _FORK_ENV_LOCK:
            prev_tp = os.environ.get(tracing.TRACEPARENT_ENV)
            prev_rs = os.environ.get("FMA_RESTARTED")
            if tp:
                os.environ[tracing.TRACEPARENT_ENV] = tp
            if restart:
                os.environ["FMA_RESTARTED"] = "1"
            try:
                self.process.start()
            finally:
                if tp:
                    if prev_tp is None:
                        os.environ.pop(tracing.TRACEPARENT_ENV, None)
                    else:
                        os.environ[tracing.TRACEPARENT_ENV] = prev_tp
                if restart:
                    if prev_rs is None:
                        os.environ.pop("FMA_RESTARTED", None)
                    else:
                        os.environ["FMA_RESTARTED"] = prev_rs
        return self._make_state("started")

    def stop(self, timeout: float = 10) -> Dict[str, Any]:
        if self.process is None:
            raise HalfMade(self.instance_id)
        if not self.process.is_alive():
            self._cleanup_log_file()
            return self._make_state("not_running")
        self.process.terminate()  # graceful: SIGTERM to the server
        self.process.join(timeout=timeout)
        if self.process.is_alive():
            try:
                os.killpg(self.process.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.process.join()
        self._cleanup_log_file()
        return self._make_state("terminated")

    def get_status(self) -> Dict[str, Any]:
        if self.process is None:
            raise HalfMade(self.instance_id)
        return self._make_state(
            "running" if self.process.is_alive() else "stopped"
        )

    # -- crash detection -----------------------------------------------------

    def start_sentinel_watcher(self, on_exit_callback) -> None:
        """Register the child's sentinel fd on the running event loop; the
        kernel makes it readable when the child dies."""
        import asyncio

        if self.process is None:
            raise HalfMade(self.instance_id)
        self._on_exit_callback = on_exit_callback
        loop = asyncio.get_running_loop()
        loop.add_reader(self.process.sentinel, self._on_sentinel_exit)
        self._sentinel_active = True

    def _on_sentinel_exit(self) -> None:
        import asyncio

        loop = asyncio.get_running_loop()
        loop.remove_reader(self.process.sentinel)
        self._sentinel_active = False
        # Reap so exitcode is populated. The sentinel can become readable a
        # beat before the child is waitable, so a zero-timeout join can miss;
        # a short blocking join is effectively instant here.
        self.process.join(timeout=2)
        if self._on_exit_callback:
            self._on_exit_callback(self.instance_id, self.process.exitcode)

    def cancel_sentinel_watcher(self) -> None:
        import asyncio

        if self._sentinel_active and self.process is not None:
            try:
                asyncio.get_running_loop().remove_reader(self.process.sentinel)
            except RuntimeError:
                pass
            self._sentinel_active = False

    # -- logs ----------------------------------------------------------------

    def _cleanup_log_file(self) -> None:
        try:
            os.unlink(self._log_file_path)
        except FileNotFoundError:
            pass

    def get_log_bytes(
        self, start: int = 0, end: Optional[int] = None
    ) -> tuple:
        """(content, total_length) for [start, end] (inclusive), capped at
        MAX_LOG_RESPONSE_BYTES. Raises LogRangeNotAvailable if start >= total."""
        try:
            total = os.path.getsize(self._log_file_path)
        except FileNotFoundError:
            total = 0
        if start >= total:
            raise LogRangeNotAvailable(start, total)
        if end is None:
            read_end = min(start + MAX_LOG_RESPONSE_BYTES - 1, total - 1)
        else:
            read_end = min(end, total - 1)
        with open(self._log_file_path, "rb") as f:
            f.seek(start)
            data = f.read(read_end - start + 1)
        return data, total
