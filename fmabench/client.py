"""The client's side of the served path: the engine child, admin verbs and
streamed completions, all on the host clock.

Process and HTTP helpers are copied from ``chip_smoke.py`` (``Child``,
``http``, ``wait_healthy``), not imported: the yardstick lives under
``fmabench/``. Load comes from ONE process with one event loop (aiohttp),
so the generator's own CPU use stays small beside the server's.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import spec

# -- the engine child -----------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The environment of every child: the checkout on the import path, and
    JAX's persistent compile cache at a fixed path inside the checkout
    (``utils/compile_cache.py`` takes the directory this variable names)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [spec.ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # a CPU rehearsal caches nothing (utils/compile_cache.py: XLA:CPU
        # executables have reloaded from disk with different numerics)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(spec.ROOT, ".xla-cache")
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update(extra or {})
    return env


class Child:
    """A child process with its output in a log file; always stopped."""

    def __init__(
        self, name: str, argv: Sequence[str], log_dir: str,
        env: Optional[Dict[str, str]] = None,
    ) -> None:
        os.makedirs(log_dir, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            list(argv), env=child_env(env), cwd=spec.ROOT, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def log_tail(self, nbytes: int = 4000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - nbytes))
            return f.read().decode(errors="replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)  # whatever is left
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def http(method: str, url: str, body: Any = None, timeout: float = 600.0) -> Any:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"{method} {url} -> {e.code}: {e.read().decode(errors='replace')}"
        ) from None
    return json.loads(raw) if raw else None


def http_text(url: str, timeout: float = 60.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def wait_healthy(url: str, child: Child, timeout: float) -> float:
    """Seconds until ``url`` answered 200; fails as soon as the child dies."""
    t0 = time.monotonic()
    last: Any = None
    while time.monotonic() - t0 < timeout:
        if child.proc.poll() is not None:
            raise RuntimeError(
                f"{child.name} exited rc={child.proc.returncode} before "
                f"{url} was healthy:\n{child.log_tail()}"
            )
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                if resp.status == 200:
                    return time.monotonic() - t0
        except (urllib.error.URLError, OSError) as e:
            last = e
        time.sleep(0.25)
    raise TimeoutError(
        f"{url} not healthy after {timeout}s ({last}):\n{child.log_tail()}"
    )


def server_argv(
    cell: "spec.Cell", config_path: str, port: int, seed: int, traced: bool,
    memory_file: str, platform: str, serve_module: str = "fmabench.serve",
) -> List[str]:
    argv = [
        sys.executable, "-m", serve_module,
        "--config-file", config_path, "--model-name", cell.config_name,
        "--memory-file", memory_file, "--require-platform", platform,
    ]
    if platform == "cpu":
        argv.append("--pallas-interpret")
    if cell.data_dir:
        argv += ["--data-dir", cell.data_dir]
    return argv + [
        "--", "--port", str(port), "--seed", str(seed),
        *cell.engine_options(traced),
    ]


# -- streamed completions ----------------------------------------------------------


class Record:
    """One request as the client saw it, host clock (``time.monotonic``)."""

    __slots__ = (
        "prompt", "max_tokens", "due", "sent", "first", "last", "tokens",
        "error", "tag", "ended",
    )

    def __init__(self, req: Dict[str, Any], due: float, tag: str = "") -> None:
        self.prompt: List[int] = req["prompt"]
        self.max_tokens: int = req["max_tokens"]
        self.due = due
        self.sent = 0.0
        self.first: Optional[float] = None
        self.last: Optional[float] = None
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.tag = tag
        self.ended: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.error is None and len(self.tokens) == self.max_tokens

    def tpot_s(self) -> Optional[float]:
        if not self.ok or len(self.tokens) < 2:
            return None
        return (self.last - self.first) / (len(self.tokens) - 1)


class TokenMeter:
    """Counts the output tokens streamed inside [open, close), in all and
    by tenth of the window (a stall shows as a thin tenth)."""

    def __init__(self) -> None:
        self.open = float("inf")
        self.close = float("inf")
        self.count = 0
        self.by_tenth = [0] * 10

    def add(self, now: float, n: int) -> None:
        if self.open <= now < self.close:
            self.count += n
            self.by_tenth[int(10 * (now - self.open) / (self.close - self.open))] += n


async def stream_one(
    session: Any, base: str, rec: Record, meter: Optional[TokenMeter] = None
) -> Record:
    """POST one greedy, fixed-length, streamed completion and fill ``rec``.
    Never raises for a failed request: the failure is the record's."""
    body = {
        "prompt": rec.prompt, "max_tokens": rec.max_tokens, "temperature": 0,
        "ignore_eos": True, "stream": True,
    }
    rec.sent = time.monotonic()
    try:
        async with session.post(base + "/v1/completions", json=body) as resp:
            if resp.status != 200:
                rec.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                rec.ended = time.monotonic()
                return rec
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                payload = raw[6:].strip()
                if payload == b"[DONE]":
                    break
                event = json.loads(payload)
                if "error" in event:
                    rec.error = str(event["error"])[:200]
                    break
                now = time.monotonic()
                for choice in event.get("choices", ()):
                    ids = choice.get("token_ids") or ()
                    if not ids:
                        continue
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                    rec.tokens.extend(ids)
                    if meter is not None:
                        meter.add(now, len(ids))
    except asyncio.CancelledError:
        rec.error = rec.error or "cancelled at window close"
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a datum
        rec.error = f"{type(e).__name__}: {e}"[:200]
    if rec.error is None and len(rec.tokens) != rec.max_tokens:
        rec.error = f"streamed {len(rec.tokens)} of {rec.max_tokens} tokens"
    rec.ended = time.monotonic()
    return rec


def new_session(limit: int) -> Any:
    import aiohttp

    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=limit, force_close=False),
        timeout=aiohttp.ClientTimeout(total=None, sock_read=600),
    )


async def run_together(
    base: str, requests: Sequence[Dict[str, Any]], tag: str
) -> List[Record]:
    """Requests sent at once, all awaited."""
    async with new_session(len(requests) + 1) as session:
        now = time.monotonic()
        recs = [Record(r, now, tag) for r in requests]
        await asyncio.gather(
            *(stream_one(session, base, rec) for rec in recs)
        )
    return recs


async def in_thread(fn: Callable[..., Any], *args: Any) -> Any:
    return await asyncio.get_running_loop().run_in_executor(None, fn, *args)
