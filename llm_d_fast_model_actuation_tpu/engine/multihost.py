"""Multi-host serving data plane: leader/follower lockstep stepping.

A multi-host engine is ONE SPMD job: every gang process must execute the
same compiled programs in the same order with the same host-side inputs,
or the collectives inside them deadlock. The control plane
(controller/gang.py) forms the gang and `jax.distributed.initialize`
joins it; this module keeps the gang in lockstep while SERVING:

  * process 0 (the **leader**) runs the normal engine loop and the HTTP
    API. Before every compiled call it broadcasts a fixed-shape control
    frame — call kind, static args (prefill bucket / chunk length), and
    the host scheduler mirrors — via
    `jax.experimental.multihost_utils.broadcast_one_to_all` (itself a
    collective, so followers block until the leader has work);
  * processes 1..N-1 (**followers**) run `follower_loop`: receive a
    frame, replay the identical compiled call on their local shards, and
    keep their device state (KV pool, scheduler arrays, RNG key) in
    lockstep. Followers never sync tokens to host — the leader alone
    talks to clients.

Determinism argument: both sides start from the same seed (the gang's
ISC options are identical), every compiled call is the same program with
the same inputs, and scheduler edges (admission, retirement) exist only
on the leader — followers import their effects through the broadcast
mirrors. vLLM's multi-host TPU serving solves this with an RPC executor
broadcasting scheduler output per step; the lockstep frame is the
XLA-native equivalent (one small collective per compiled dispatch).

The frame is FIXED SHAPE for a given engine config, so the broadcast
compiles exactly once.
"""

from __future__ import annotations

import hashlib
import hmac
import logging
import os
import socket
import socketserver
import threading
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

logger = logging.getLogger(__name__)

#: shared-secret env for heartbeat authentication: every gang member gets
#: the same value from the coordinator's ISC env. Unset falls back to a
#: fixed default — the token then only proves "same coordinator address",
#: which still stops a stray prober on a hostNetwork node from keeping a
#: half-dead gang looking alive.
GANG_HB_SECRET_ENV = "FMA_GANG_HB_SECRET"


def gang_heartbeat_token(coordinator_address: str) -> str:
    """Per-gang heartbeat token: HMAC of the coordinator address under the
    shared secret. Binds a ping to THIS gang — two gangs whose heartbeat
    ports collide across restarts (the port is derived, not reserved)
    can no longer accept each other's pings, and an unauthenticated
    writer can't refresh a member's liveness."""
    secret = os.environ.get(GANG_HB_SECRET_ENV, "") or "fma-gang"
    return hmac.new(
        secret.encode(), coordinator_address.encode(), hashlib.sha256
    ).hexdigest()[:16]

#: Heartbeat port = coordinator port + this offset. The gang coordinator
#: draws per-gang coordinator ports from [base, base+4096) (controller/
#: gang.py), so base+4096.. is collision-free against other gangs'
#: coordinators on the same hostNetwork node.
HEARTBEAT_PORT_OFFSET = 4096

#: Exit code for "a gang peer died while the data plane may be blocked in
#: a collective" — the launcher's sentinel sees the process exit and the
#: crash chain (STOPPED -> notifier -> controller deletes the requester ->
#: gang degrades -> re-forms) takes over, the same path a single-host
#: engine crash takes (launcher/instance.py).
EXIT_GANG_PEER_LOST = 13


class GangWatchdog:
    """Data-plane failure detector for a lockstep gang.

    The lockstep protocol is built on collectives, and a collective whose
    participant died never completes — a wedged gang serves nothing and
    looks alive. The reference's failure chain is process-level (vLLM
    crash -> launcher sentinel -> controller deletes the server pod); this
    gives the gang's data plane the same property: any member death
    converts, within `timeout` seconds, into every other member exiting
    non-zero, which the per-member launchers' sentinels all see.

    Star topology over the leader's host (every member already knows the
    coordinator address; no extra discovery):

      * the leader runs a tiny TCP responder on coordinator_port +
        HEARTBEAT_PORT_OFFSET and tracks when each follower last pinged;
        a follower silent for `timeout` seconds (or never arrived within
        `join_grace`) kills the leader;
      * followers ping every `interval` seconds; a leader unreachable for
        `timeout` seconds kills the follower.

    A follower death thus kills the leader directly, and the leader's
    death cascades to the remaining followers — whole-gang teardown from
    any single fault, without requiring full pairwise connectivity.

    Heartbeats ride their own threads + sockets, never the collective
    stream, so a gang blocked in a healthy long collective (big prefill)
    keeps answering and is NOT torn down: timeouts fire only when a
    process is actually gone (its responder/prober dies with it).
    """

    def __init__(
        self,
        process_id: int,
        num_processes: int,
        coordinator_address: str,
        interval: float = 2.0,
        timeout: float = 20.0,
        join_grace: float = 60.0,
        on_death: Optional[Callable[[str], None]] = None,
    ) -> None:
        host, _, port = coordinator_address.rpartition(":")
        self.process_id = process_id
        self.num_processes = num_processes
        self.leader_host = host
        self.hb_port = int(port) + HEARTBEAT_PORT_OFFSET
        #: per-gang auth token (see gang_heartbeat_token): carried in
        #: every ping, verified by the responder — an unauthenticated
        #: ping refreshes nothing and gets no "ok"
        self.token = gang_heartbeat_token(coordinator_address)
        # a timeout needs several missed pings' slack, or scheduler jitter
        # on a single late ping reads as a death: keep >= 4 intervals per
        # timeout window by shrinking the interval for small timeouts
        self.interval = min(interval, max(0.05, timeout / 4.0))
        self.timeout = timeout
        self.join_grace = max(join_grace, timeout)
        self._on_death = on_death or self._die
        self._stop = threading.Event()
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        self._threads: list = []
        #: leader: follower pid -> monotonic last-heard
        self._last_seen: Dict[int, float] = {}

    @staticmethod
    def _die(reason: str) -> None:
        logger.critical(
            "gang watchdog: %s — exiting %d so the launcher sentinel "
            "tears this member down (the data plane may be wedged in a "
            "collective and cannot unwind in-process)",
            reason, EXIT_GANG_PEER_LOST,
        )
        # not sys.exit: the lockstep thread may be blocked inside a
        # collective that will never return; only the process can die
        os._exit(EXIT_GANG_PEER_LOST)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self.num_processes <= 1:
            return
        if self.process_id == 0:
            self._start_responder()
            t = threading.Thread(
                target=self._leader_monitor, daemon=True,
                name="gang-hb-monitor",
            )
        else:
            t = threading.Thread(
                target=self._follower_prober, daemon=True,
                name="gang-hb-prober",
            )
        t.start()
        self._threads.append(t)

    def stop(self) -> None:
        """Clean shutdown (leader broadcast SHUTDOWN was delivered): stop
        probing/monitoring so the orderly teardown isn't misread as a
        death."""
        self._stop.set()
        if self._server is not None:
            try:
                self._server.shutdown()
                self._server.server_close()
            except Exception:  # noqa: BLE001
                pass

    # -- leader side ---------------------------------------------------------

    def _start_responder(self) -> None:
        last_seen = self._last_seen
        token = self.token

        class Handler(socketserver.StreamRequestHandler):
            def handle(self) -> None:
                try:
                    line = self.rfile.readline(96).decode().split()
                    # "hb <pid> <token>": the token must verify or the
                    # ping neither refreshes liveness nor gets an "ok" —
                    # a stray/foreign prober can't keep a dead member
                    # looking alive (constant-time compare: the token is
                    # a shared-secret MAC, not a public cookie)
                    if (
                        len(line) == 3
                        and line[0] == "hb"
                        and hmac.compare_digest(line[2], token)
                    ):
                        last_seen[int(line[1])] = time.monotonic()
                        self.wfile.write(b"ok\n")
                except (ValueError, OSError):
                    pass

        class _HBServer(socketserver.ThreadingTCPServer):
            # confined to the watchdog's server; mutating the stdlib class
            # attribute would flip SO_REUSEADDR on for unrelated servers
            allow_reuse_address = True

        try:
            self._server = _HBServer(("0.0.0.0", self.hb_port), Handler)
        except OSError as e:
            # name the port-derivation scheme: "address already in use" on
            # a number nobody configured is otherwise undebuggable
            raise RuntimeError(
                f"gang heartbeat responder failed to bind "
                f"0.0.0.0:{self.hb_port} (= coordinator port "
                f"{self.hb_port - HEARTBEAT_PORT_OFFSET} + "
                f"HEARTBEAT_PORT_OFFSET {HEARTBEAT_PORT_OFFSET}; the "
                f"gang coordinator draws coordinator ports from a range "
                f"whose +{HEARTBEAT_PORT_OFFSET} offset must stay free "
                f"on this node): {e}"
            ) from e
        self._server.daemon_threads = True
        t = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="gang-hb-server",
        )
        t.start()
        self._threads.append(t)

    def _leader_monitor(self) -> None:
        started = time.monotonic()
        expected = set(range(1, self.num_processes))
        while not self._stop.wait(self.interval):
            now = time.monotonic()
            for pid in expected:
                seen = self._last_seen.get(pid)
                if seen is None:
                    # jax.distributed.initialize returned, so the member
                    # process existed; its first ping should land within
                    # an interval or two
                    if now - started > self.join_grace:
                        self._on_death(
                            f"follower {pid} never sent a heartbeat "
                            f"within {self.join_grace:.0f}s of gang start"
                        )
                        return
                elif now - seen > self.timeout:
                    self._on_death(
                        f"follower {pid} heartbeat silent for "
                        f"{now - seen:.1f}s (> {self.timeout:.0f}s)"
                    )
                    return

    # -- follower side -------------------------------------------------------

    def _ping(self) -> bool:
        try:
            with socket.create_connection(
                (self.leader_host, self.hb_port), timeout=self.interval + 1
            ) as s:
                s.sendall(f"hb {self.process_id} {self.token}\n".encode())
                s.settimeout(self.interval + 1)
                return s.recv(8).startswith(b"ok")
        except OSError:
            return False

    def _follower_prober(self) -> None:
        last_ok = time.monotonic()
        reached = False  # leader responder answered at least once
        while not self._stop.wait(self.interval):
            if self._ping():
                last_ok = time.monotonic()
                reached = True
                continue
            silent = time.monotonic() - last_ok
            # before first contact the leader may still be compiling /
            # binding its responder: allow the same grace the leader gives
            # followers, then the steady-state timeout applies
            allowed = self.timeout if reached else self.join_grace
            if silent > allowed:
                self._on_death(
                    f"leader heartbeat unreachable for {silent:.1f}s "
                    f"(> {allowed:.0f}s)"
                )
                return

KIND_IDLE = 0
KIND_PREFILL = 1
KIND_CHUNK = 2
KIND_SLEEP = 3
KIND_WAKE = 4
KIND_SHUTDOWN = 5
KIND_PREFILL_SUFFIX = 6  #: prefix-cache hit: replay the continue program


def _frame_template(cfg) -> Dict[str, np.ndarray]:
    b, p = cfg.max_batch, cfg.pages_per_seq
    return {
        "kind": np.zeros((), np.int32),
        #: prefill bucket | chunk T | sleep level
        "arg": np.zeros((), np.int32),
        #: prefill slot | sleep release flag
        "arg2": np.zeros((), np.int32),
        "seq_len": np.zeros((), np.int32),
        #: suffix prefill: absolute position of the first suffix token
        "start": np.zeros((), np.int32),
        "temp": np.zeros((), np.float32),
        "top_p": np.ones((), np.float32),
        "tokens": np.zeros((cfg.seq_len,), np.int32),
        #: chunk: rebuild device scheduler state from the mirrors below
        "reupload": np.zeros((), np.int32),
        #: suffix prefill: thread the returned RNG key (1) or discard it
        #: (0 — non-final chunked-prefill segments)
        "advance_key": np.ones((), np.int32),
        "want_plp": np.zeros((), np.int32),
        "lt": np.zeros((b,), np.int32),
        "pos": np.zeros((b,), np.int32),
        "budget": np.zeros((b,), np.int32),
        "temps": np.zeros((b,), np.float32),
        "topps": np.ones((b,), np.float32),
        # penalties are rejected for gangs (engine.add_request): these stay
        # zero, which makes the samplers count-independent, so the [b,vocab]
        # count arrays themselves never need to cross the frame
        "pres": np.zeros((b,), np.float32),
        "freqs": np.zeros((b,), np.float32),
        "page_table": np.zeros((b, p), np.int32),
        #: per-slot RNG key data (per-request seed streams)
        "skeys": np.zeros((b, 2), np.uint32),
        #: per-slot eos sensitivity (ignore_eos requests = 0)
        "eos_on": np.ones((b,), np.int32),
    }


def _broadcast(frame: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    from jax.experimental import multihost_utils

    out = multihost_utils.broadcast_one_to_all(frame)
    return {k: np.asarray(v) for k, v in out.items()}


class LockstepLeader:
    """Installed on the leader's engine as `engine.lockstep`; the engine
    calls these hooks immediately before its compiled dispatches."""

    def __init__(self, engine: Any) -> None:
        if engine.cfg.speculative_mtp:
            raise ValueError(
                "--speculative-mtp is not supported for multi-host gangs "
                "(the lockstep frame carries no drafts)"
            )
        self.engine = engine
        self._template = _frame_template(engine.cfg)

    def _mirrors(self, f: Dict[str, np.ndarray]) -> None:
        e = self.engine
        f["lt"] = e._last_tokens.copy()
        f["pos"] = e._positions.copy()
        f["budget"] = e._budgets.copy()
        f["temps"] = e._temps.copy()
        f["topps"] = e._topps.copy()
        f["pres"] = e._pres.copy()
        f["freqs"] = e._freqs.copy()
        f["page_table"] = e._page_table.copy()
        f["skeys"] = e._slot_keys.copy()
        f["eos_on"] = e._eos_on.copy()

    def _send(self, **fields: Any) -> None:
        f = dict(self._template)
        self._mirrors(f)
        for k, v in fields.items():
            f[k] = np.asarray(v, f[k].dtype)
        _broadcast(f)

    # -- hooks ---------------------------------------------------------------

    def prefill(self, req: Any, bucket: int, want_plp: bool = False) -> None:
        tokens = np.zeros((self.engine.cfg.seq_len,), np.int32)
        tokens[: len(req.prompt)] = req.prompt
        self._send(
            kind=KIND_PREFILL,
            arg=bucket,
            arg2=req.slot,
            seq_len=len(req.prompt),
            temp=req.temperature,
            top_p=req.top_p,
            tokens=tokens,
            want_plp=int(want_plp),
        )

    def prefill_suffix(
        self,
        req: Any,
        bucket: int,
        start: int,
        seg_len: int = -1,
        advance_key: bool = True,
        want_plp: bool = False,
    ) -> None:
        if seg_len < 0:
            seg_len = len(req.prompt) - start
        seg = req.prompt[start : start + seg_len]
        tokens = np.zeros((self.engine.cfg.seq_len,), np.int32)
        tokens[: len(seg)] = seg
        self._send(
            kind=KIND_PREFILL_SUFFIX,
            arg=bucket,
            arg2=req.slot,
            seq_len=len(seg),
            start=start,
            temp=req.temperature,
            top_p=req.top_p,
            tokens=tokens,
            advance_key=int(advance_key),
            want_plp=int(want_plp),
        )

    def chunk(self, T: int, reupload: bool) -> None:
        self._send(kind=KIND_CHUNK, arg=T, reupload=int(reupload))

    def sleep(self, level: int, release: bool) -> None:
        self._send(kind=KIND_SLEEP, arg=level, arg2=int(release))

    def wake(self) -> None:
        self._send(kind=KIND_WAKE)

    def shutdown(self) -> None:
        self._send(kind=KIND_SHUTDOWN)


def follower_loop(engine: Any, sleeper: Optional[Any] = None) -> None:
    """Run a follower process until the leader broadcasts SHUTDOWN.

    `engine` must be constructed identically to the leader's (same config,
    same seed, same mesh plan) — the gang ships identical ISC options to
    every member, so this holds by construction.
    """
    template = _frame_template(engine.cfg)
    while True:
        f = _broadcast(template)
        kind = int(f["kind"])
        if kind == KIND_SHUTDOWN:
            logger.info("follower: leader shut down")
            return
        if kind == KIND_PREFILL:
            _replay_prefill(engine, f)
        elif kind == KIND_PREFILL_SUFFIX:
            _replay_prefill_suffix(engine, f)
        elif kind == KIND_CHUNK:
            _replay_chunk(engine, f)
        elif kind == KIND_SLEEP and sleeper is not None:
            sleeper.sleep(int(f["arg"]), release=bool(int(f["arg2"])))
        elif kind == KIND_WAKE and sleeper is not None:
            sleeper.wake_up()


def _sync_mirrors(engine: Any, f: Dict[str, np.ndarray]) -> None:
    engine._last_tokens[:] = f["lt"]
    engine._positions[:] = f["pos"]
    engine._budgets[:] = f["budget"]
    engine._temps[:] = f["temps"]
    engine._topps[:] = f["topps"]
    engine._pres[:] = f["pres"]
    engine._freqs[:] = f["freqs"]
    engine._page_table[:] = f["page_table"]
    engine._slot_keys[:] = f["skeys"]
    engine._eos_on[:] = f["eos_on"]


def _replay_prefill(engine: Any, f: Dict[str, np.ndarray]) -> None:
    bucket = int(f["arg"])
    slot = int(f["arg2"])
    n = int(f["seq_len"])
    _sync_mirrors(engine, f)
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :] = f["tokens"][:bucket]
    seq_lens = np.array([n], np.int32)
    table = engine._page_table[slot : slot + 1]
    temp = np.asarray([float(f["temp"])], np.float32)
    topp = np.asarray([float(f["top_p"])], np.float32)
    counts_row = engine._token_counts[slot : slot + 1]
    zero = np.zeros((1,), np.float32)
    fn = (
        engine._prefill_plp_fn
        if int(f.get("want_plp", 0))
        else engine._prefill_fn
    )
    _tok, _lp, _av, _ai, _plp, cache, new_key = fn(
        engine.params,
        tokens,
        seq_lens,
        engine.pool.as_tuple(),
        table,
        temp,
        topp,
        counts_row,
        zero,
        zero,
        engine._slot_keys[slot],
        # biased requests are rejected for gangs; a zero row keeps the
        # program signature
        np.zeros((1, engine.cfg.model.vocab_size), np.float32),
    )
    engine._slot_keys[slot] = np.asarray(new_key)
    engine.pool.replace(cache)
    # no host sync: the leader alone consumes tokens


def _replay_prefill_suffix(engine: Any, f: Dict[str, np.ndarray]) -> None:
    bucket = int(f["arg"])
    slot = int(f["arg2"])
    n = int(f["seq_len"])
    _sync_mirrors(engine, f)
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :] = f["tokens"][:bucket]
    start = np.array([int(f["start"])], np.int32)
    suffix_lens = np.array([n], np.int32)
    table = engine._page_table[slot : slot + 1]
    temp = np.asarray([float(f["temp"])], np.float32)
    topp = np.asarray([float(f["top_p"])], np.float32)
    counts_row = engine._token_counts[slot : slot + 1]
    zero = np.zeros((1,), np.float32)
    # targets feed prompt-logprob gathering; followers discard outputs,
    # so zeros keep the program shape without carrying data in the frame
    fn = (
        engine._suffix_prefill_plp_fn
        if int(f.get("want_plp", 0))
        else engine._suffix_prefill_fn
    )
    _tok, _lp, _av, _ai, _plp, cache, new_key = fn(
        engine.params,
        tokens,
        np.zeros_like(tokens),
        start,
        suffix_lens,
        engine.pool.as_tuple(),
        table,
        temp,
        topp,
        counts_row,
        zero,
        zero,
        engine._slot_keys[slot],
        np.zeros((1, engine.cfg.model.vocab_size), np.float32),
    )
    if int(f["advance_key"]):
        engine._slot_keys[slot] = np.asarray(new_key)
    engine.pool.replace(cache)


def _replay_chunk(engine: Any, f: Dict[str, np.ndarray]) -> None:
    T = int(f["arg"])
    if int(f["reupload"]) or engine._dev is None:
        _sync_mirrors(engine, f)
        engine._upload_sched()
    d = engine._dev
    (
        _toks, _lps, _avs, _ais, lt, pos, budget, cache, counts_dev,
        skeys_dev,
    ) = engine._chunk_fn(T)(
        engine.params,
        d["lt"],
        d["pos"],
        d["budget"],
        engine.pool.as_tuple(),
        d["pt"],
        d["temps"],
        d["topp"],
        d["counts"],
        d["pres"],
        d["freq"],
        d["skeys"],
        d["eos_on"],
        d["bias"],
    )
    engine.pool.replace(cache)
    engine._dev = {
        "lt": lt, "pos": pos, "budget": budget,
        "pt": d["pt"], "temps": d["temps"], "topp": d["topp"],
        "counts": counts_dev, "pres": d["pres"], "freq": d["freq"],
        "skeys": skeys_dev, "eos_on": d["eos_on"], "bias": d["bias"],
    }
