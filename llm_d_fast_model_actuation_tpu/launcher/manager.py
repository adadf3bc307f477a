"""Multi-instance manager: CRUDL over engine instances + revisioned events.

Mirrors the reference's `VllmMultiProcessManager` (launcher.py:344-515): a
monotonically increasing revision counter stamped on every lifecycle event
(CREATED / STOPPED / DELETED), duplicate-ID create is an error (REST maps it
to 409), stop is graceful-then-kill, and a crashed child produces a STOPPED
event with its exit code via the sentinel watcher.

TPU delta: a `ChipLedger` records which chip sets are held by live instance
processes, and the manager *enforces* it: on TPU a chip has exactly one
process-holder at a time (a second PJRT client blocks in init), so creating
an instance whose chips overlap an AWAKE holder can only wedge — the
launcher refuses with 409. Overlap with holders that are all ASLEEP (devices
released; see engine/sleep.py) is the product's time-sharing path and is
allowed. The dual-pods controller remains the party that orchestrates who
sleeps when; the ledger is the node-local safety net against a controller
bug silently double-booking a chip.
"""

from __future__ import annotations

import json
import logging
import random
import socket
import threading
import time
import urllib.error
import urllib.request
import uuid as uuidlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from prometheus_client import Gauge, Histogram

from ..utils import faults, tracing
from ..utils.events import EventBroadcaster
from .chiptranslator import ChipTranslator
from .instance import EngineInstance, InstanceConfig

logger = logging.getLogger(__name__)

#: Launcher -> engine-child admin RPC latency (the hop between the
#: controller-visible fma_http_latency_seconds and the engine's own verb
#: histograms — without it a slow actuation cannot be attributed to this
#: leg). One observation per HTTP attempt; `outcome` separates the retry
#: vocabulary: ok / http_<code> / refused (retried) / timeout /
#: unreachable. Exposed by the launcher's GET /metrics (docs/metrics.md).
LAUNCHER_RPC_SECONDS = Histogram(
    "fma_launcher_rpc_seconds",
    "Latency of launcher -> engine-child admin RPCs",
    ["verb", "outcome"],
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 2, 5, 15, 60, 300),
)

# Fleet rollup (docs/launcher.md "The fleet block"): node-level SLO /
# goodput / demand aggregates over every live engine child's GET
# /v1/stats, refreshed by fleet_rollup() on instance-list and /metrics
# reads — the one-scrape fleet view the multi-model scheduler (ROADMAP
# item 1) consumes.
LAUNCHER_FLEET_INSTANCES = Gauge(
    "fma_launcher_fleet_instances",
    "Engine instances by stats-poll outcome",
    ["state"],  # reporting | unreachable
)
LAUNCHER_FLEET_QUEUE_DEPTH = Gauge(
    "fma_launcher_fleet_queue_depth",
    "Waiting + in-flight requests summed over reporting instances",
)
LAUNCHER_FLEET_ARRIVAL_RATE = Gauge(
    "fma_launcher_fleet_arrival_rate",
    "Summed per-instance request arrival-rate EWMAs (requests/s)",
)
LAUNCHER_FLEET_SLO_ATTAINMENT = Gauge(
    "fma_launcher_fleet_slo_attainment",
    "Fraction of SLO-judged requests that met every configured target "
    "across the fleet (1.0 when nothing has been judged yet)",
)
LAUNCHER_FLEET_GOODPUT_TOKENS = Gauge(
    "fma_launcher_fleet_goodput_tokens",
    "Cumulative generated tokens from SLO-met requests, fleet-wide",
)
LAUNCHER_FLEET_ACTUATIONS_PER_HOUR = Gauge(
    "fma_launcher_fleet_actuations_per_hour",
    "Summed per-instance actuation rates (swap+sleep+wake per uptime "
    "hour)",
)

STATUS_STOPPED = "stopped"
STATUS_RUNNING = "running"

# probe_instance_state vocabulary: "still booting" (connected but no answer
# yet) and "crashed" (nothing listening) are DIFFERENT failure domains — a
# supervisor must never restart an instance that is merely slow to bind.
PROBE_AWAKE = "awake"
PROBE_RELEASED = "released"  # asleep AND devices released: chip is free
PROBE_REFUSED = "refused"  # nothing listening: crashed or not yet bound
PROBE_TIMEOUT = "timeout"  # listening but slow: booting / busy, NOT dead
PROBE_ERROR = "error"  # unparseable options, DNS, test fakes, ...


class ChipConflict(Exception):
    """Requested chips overlap an instance that is (or may be) awake."""

    def __init__(self, instance_id: str, blockers: List[str]) -> None:
        super().__init__(
            f"instance {instance_id}: chips held by awake (or not-yet-probeable) "
            f"instance(s) {blockers}; a TPU chip has one holder — sleep them first"
        )
        self.instance_id = instance_id
        self.blockers = blockers


class SwapFailed(Exception):
    """The engine child rejected (or never answered) a model hot-swap."""

    def __init__(self, instance_id: str, status: int, detail: str) -> None:
        super().__init__(
            f"swap of instance {instance_id} failed ({status}): {detail}"
        )
        self.instance_id = instance_id
        self.status = status
        self.detail = detail


class PrefetchFailed(Exception):
    """The engine child rejected (or never answered) a prefetch verb."""

    def __init__(self, instance_id: str, status: int, detail: str) -> None:
        super().__init__(
            f"prefetch on instance {instance_id} failed ({status}): {detail}"
        )
        self.instance_id = instance_id
        self.status = status
        self.detail = detail


class StatsFailed(Exception):
    """The engine child never answered a stats poll (fleet rollup marks
    the instance unreachable instead of failing the whole read)."""

    def __init__(self, instance_id: str, status: int, detail: str) -> None:
        super().__init__(
            f"stats on instance {instance_id} failed ({status}): {detail}"
        )
        self.instance_id = instance_id
        self.status = status
        self.detail = detail


class MigrateFailed(Exception):
    """A live-request migration step failed (or was refused). Status 409
    carries the engine's explicit precondition refusal — identity
    mismatch, spent fence, no capacity —
    after which nothing was displaced. Any other status means recovery
    already ran on the engines (source resumed locally or aborted the
    fenced bundle); the streams survived, the handoff didn't."""

    def __init__(self, instance_id: str, status: int, detail: str) -> None:
        super().__init__(
            f"migrate on instance {instance_id} failed ({status}): {detail}"
        )
        self.instance_id = instance_id
        self.status = status
        self.detail = detail


class DrainFailed(Exception):
    """A node-drain pass could not move the instance's remaining live
    work to a sibling (no eligible sibling, or a migrate pass failed)."""

    def __init__(self, instance_id: str, status: int, detail: str) -> None:
        super().__init__(
            f"drain of instance {instance_id} failed ({status}): {detail}"
        )
        self.instance_id = instance_id
        self.status = status
        self.detail = detail


def probe_instance_state(
    instance: "EngineInstance", timeout: float = 2.0
) -> str:
    """Classified probe of an instance's engine admin API (one of the
    PROBE_* constants). Unlike a bare reachable/unreachable check this
    separates connection-refused (nothing bound to the port: crashed, or
    the child hasn't reached its listen() yet) from timeout (something IS
    listening but slow to answer: booting, compiling, or busy) — the
    supervisor and chip-exclusivity logic weigh those differently."""
    try:
        from ..engine.server import parse_engine_options

        port = parse_engine_options(instance.config.options).port
    except Exception:
        return PROBE_ERROR
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/is_sleeping", timeout=timeout
        ) as resp:
            body = json.loads(resp.read() or b"{}")
    except urllib.error.URLError as e:
        reason = getattr(e, "reason", None)
        if isinstance(reason, ConnectionRefusedError):
            return PROBE_REFUSED
        if isinstance(reason, (TimeoutError, socket.timeout)):
            return PROBE_TIMEOUT
        return PROBE_ERROR
    except ConnectionRefusedError:
        return PROBE_REFUSED
    except (TimeoutError, socket.timeout):
        return PROBE_TIMEOUT
    except Exception:
        return PROBE_ERROR
    if body.get("is_sleeping", False) and body.get(
        "devices_released", False
    ):
        return PROBE_RELEASED
    return PROBE_AWAKE


def probe_instance_awake(instance: "EngineInstance") -> Optional[bool]:
    """Ask the instance's engine admin API whether it still holds its chips.

    Returns True ("awake": serving, or sleeping with the TPU client still
    open — either way the chip is held), False (asleep AND devices released
    — the chip is genuinely free), or None (engine not reachable — still
    booting, crashed, or a test fake). For the supervisor-facing
    distinction between those None cases, use probe_instance_state."""
    state = probe_instance_state(instance)
    if state == PROBE_AWAKE:
        return True
    if state == PROBE_RELEASED:
        return False
    return None


class ChipLedger:
    """Node-local truth of which live instance holds which chips (and which
    model each holder currently serves — hot-swap rebinds the model without
    touching the chip set, so the holder entry survives swaps unchanged)."""

    def __init__(self) -> None:
        self._held: Dict[str, List[str]] = {}  # instance_id -> chip_ids
        self._models: Dict[str, str] = {}  # instance_id -> served model
        #: instance_id -> model hinted/staged via the prefetch verb: the
        #: controller's "predicted next model" for this holder. Cleared
        #: when the hint is consumed (swap to that model), aborted, or
        #: the holder releases its chips.
        self._prefetched: Dict[str, str] = {}
        #: instance_id -> compact tiered-pool summary (pooled models,
        #: deduped host residency, dedup savings, disk-tier bytes, staged
        #: manifests) from the holder's last swap/prefetch answer — what a
        #: multi-model scheduler reads to pick a warm victim/target
        #: without an extra engine round trip.
        self._pools: Dict[str, Dict[str, Any]] = {}
        #: instance_id -> transfer mode of the holder's last swap ("off" |
        #: "int8" | "fp8"): whether this holder actuates compressed
        #: (docs/perf.md "Compressed actuation") — the byte-cost signal a
        #: scheduler weighs against the models' numerics requirements.
        self._quant: Dict[str, str] = {}

    def overlapping(
        self, chip_ids: Optional[List[str]], exclude: Optional[str] = None
    ) -> List[str]:
        """Instance IDs whose recorded chip sets overlap `chip_ids`."""
        chips = set(chip_ids or [])
        return [
            iid
            for iid, held in self._held.items()
            if iid != exclude and chips & set(held)
        ]

    def acquire(self, instance_id: str, chip_ids: Optional[List[str]]) -> List[str]:
        """Record ownership; returns the list of instance IDs whose chip sets
        overlap (empty = clean placement)."""
        overlaps = self.overlapping(chip_ids, exclude=instance_id)
        self._held[instance_id] = sorted(set(chip_ids or []))
        return overlaps

    def release(self, instance_id: str) -> None:
        self._held.pop(instance_id, None)
        self._models.pop(instance_id, None)
        self._prefetched.pop(instance_id, None)
        self._pools.pop(instance_id, None)
        self._quant.pop(instance_id, None)

    def set_model(self, instance_id: str, model: str) -> None:
        """Record which model a holder serves (updated on hot-swap). A
        swap to the prefetched model consumes the prefetch hint."""
        if instance_id in self._held:
            self._models[instance_id] = model
            if self._prefetched.get(instance_id) == model:
                self._prefetched.pop(instance_id, None)

    def set_prefetched(self, instance_id: str, model: Optional[str]) -> None:
        """Record (or with None, clear) the model a holder has staged via
        the prefetch verb."""
        if model is None:
            self._prefetched.pop(instance_id, None)
        elif instance_id in self._held:
            self._prefetched[instance_id] = model

    def set_pool(
        self, instance_id: str, pool: Optional[Dict[str, Any]]
    ) -> None:
        """Record the holder's tiered-pool shape from an engine swap /
        prefetch answer (None or a pool-less answer clears nothing — the
        last known summary stays until the holder releases its chips)."""
        if pool is None or instance_id not in self._held:
            return
        chunks = pool.get("chunks") or {}
        self._pools[instance_id] = {
            "models": list(pool.get("models") or []),
            "bytes_used": pool.get("bytes_used", 0),
            "budget_bytes": pool.get("budget_bytes", 0),
            "dedup_saved_bytes": chunks.get("dedup_saved_bytes", 0),
            "disk_bytes": chunks.get("disk_bytes", 0),
            "staged_manifests": list(pool.get("staged_manifests") or []),
        }

    def set_quant(self, instance_id: str, quant: Optional[str]) -> None:
        """Record the transfer mode of a holder's last swap answer (None
        / unknown answers leave the last known value)."""
        if quant and instance_id in self._held:
            self._quant[instance_id] = quant

    def quants(self) -> Dict[str, str]:
        return dict(self._quant)

    def holders(self) -> Dict[str, List[str]]:
        return dict(self._held)

    def models(self) -> Dict[str, str]:
        return dict(self._models)

    def prefetched(self) -> Dict[str, str]:
        return dict(self._prefetched)

    def pools(self) -> Dict[str, Dict[str, Any]]:
        return dict(self._pools)


@dataclass
class RestartPolicy:
    """Supervised-restart knobs for crashed engine children.

    ``budget`` restarts per crash loop (0 disables supervision — the
    launcher then only reports the death, the pre-existing behavior, and
    the dual-pods controller heals by re-pairing). Delays grow
    ``backoff_s * 2**attempt`` up to ``backoff_max_s``, with up to
    ``jitter_frac`` random extra so a node full of children crashed by one
    cause doesn't restart in lockstep. A child that stays up longer than
    ``reset_window_s`` earns its crash counter back — the budget bounds
    crash *loops*, not total restarts over a long instance lifetime."""

    budget: int = 0
    backoff_s: float = 0.5
    backoff_max_s: float = 30.0
    jitter_frac: float = 0.2
    reset_window_s: float = 300.0


@dataclass
class _RestartState:
    attempts: int = 0
    last_crash: float = 0.0
    timer: Optional[threading.Timer] = None
    #: set by _cancel_restart under the restart lock; a timer body that
    #: already started (Timer.cancel is a no-op then) re-checks this
    #: before forking, so an explicit stop can never race an orphan child
    cancelled: bool = False


class EngineProcessManager:
    def __init__(
        self,
        translator: ChipTranslator,
        log_dir: str = "",
        kickoff=None,
        enforce_chip_exclusivity: bool = True,
        awake_probe: Optional[
            Callable[["EngineInstance"], Optional[bool]]
        ] = None,
        restart_policy: Optional[RestartPolicy] = None,
    ) -> None:
        self.instances: Dict[str, EngineInstance] = {}
        self.translator = translator
        if log_dir:
            import os

            os.makedirs(log_dir, exist_ok=True)
        self.log_dir = log_dir
        self.ledger = ChipLedger()
        self.broadcaster = EventBroadcaster()
        self._revision = 0
        # create/sentinel publish on the loop thread; stop_instance publishes
        # from the REST handler's executor thread — revision minting and the
        # buffer append must be one atomic step or a watcher can skip events
        self._rev_lock = threading.Lock()
        self._kickoff = kickoff
        # With a fake kickoff there is no engine admin API to probe, so the
        # sleep state of an overlapping holder is unknowable — enforcement
        # stays opt-in for such managers (tests pass a probe or disable).
        self.enforce_chip_exclusivity = enforce_chip_exclusivity
        self._awake_probe = awake_probe or probe_instance_awake
        # Crash supervision (docs/operations.md "Self-healing"): a child
        # death becomes a backoff-scheduled in-place restart instead of a
        # wait for the controller's minutes-long re-pair path.
        self.restart_policy = restart_policy
        self._restart_states: Dict[str, _RestartState] = {}
        # RLock: _restart_instance holds it across its whole body (so a
        # concurrent stop_instance serializes against the fork) and its
        # spawn-failure path re-enters via _restart_allowed/_schedule
        self._restart_lock = threading.RLock()
        self._loop = None  # captured from the sentinel callback's loop
        # fleet_rollup cache: instance-list reads and /metrics scrapes
        # both refresh the rollup; a short TTL keeps back-to-back reads
        # from double-polling every child
        self._fleet_lock = threading.Lock()
        self._fleet_cache: Optional[tuple] = None  # (monotonic_t, block)

    # -- revisions -----------------------------------------------------------

    @property
    def revision(self) -> int:
        return self._revision

    def _next_revision(self) -> int:
        self._revision += 1
        return self._revision

    def _publish(self, event_type: str, obj: Dict[str, Any]) -> int:
        """Mint-and-append atomically (cross-thread safe); returns the
        revision stamped on the event."""
        with self._rev_lock:
            rev = self._next_revision()
            obj["revision"] = rev
            self.broadcaster.publish_nowait(rev, {"type": event_type, "object": obj})
        return rev

    # -- CRUDL ---------------------------------------------------------------

    def create_instance(
        self, config: InstanceConfig, instance_id: Optional[str] = None
    ) -> Dict[str, Any]:
        """Traced entry: the span is active across the fork, so the child
        inherits it via FMA_TRACEPARENT (instance.start stamps the env)
        and its engine.start span joins this trace."""
        with tracing.span(
            "launcher.create_instance", instance=instance_id or ""
        ):
            return self._create_instance_impl(config, instance_id)

    def _create_instance_impl(
        self, config: InstanceConfig, instance_id: Optional[str] = None
    ) -> Dict[str, Any]:
        iid = instance_id or str(uuidlib.uuid4())
        if iid in self.instances:
            raise ValueError(f"instance {iid} already exists")
        if self._kickoff is None:
            # Real engine path: validate the options string pre-fork so a bad
            # config is a 422 at create time, not a crash discovered later.
            from ..engine.server import parse_engine_options
            from .instance import InvalidInstanceConfig

            try:
                parse_engine_options(config.options)
            except Exception as e:
                raise InvalidInstanceConfig(f"invalid engine options: {e}")
        overlaps = self.ledger.overlapping(config.chip_ids, exclude=iid)
        if overlaps and self.enforce_chip_exclusivity:
            # Allowed only if EVERY overlapping holder is verifiably asleep
            # with devices released. Unreachable == possibly booting ==
            # treated awake: refusing a race beats wedging the chip.
            blockers = []
            for other in overlaps:
                inst = self.instances.get(other)
                if inst is None:
                    # stale ledger entry (a failed create); drop, not block
                    self.ledger.release(other)
                    continue
                if self._awake_probe(inst) is not False:
                    blockers.append(other)
            if blockers:
                raise ChipConflict(iid, blockers)
        elif overlaps:
            logger.warning(
                "instance %s chips overlap live instances %s "
                "(enforcement off: controller must ensure they are asleep)",
                iid,
                overlaps,
            )
        kwargs = {} if self._kickoff is None else {"kickoff": self._kickoff}
        instance = EngineInstance(
            iid, config, self.translator, log_dir=self.log_dir, **kwargs
        )
        result = instance.start()
        # record ownership only once the process actually exists — a failed
        # start must not leak a chips hold
        self.ledger.acquire(iid, config.chip_ids)
        try:
            from ..engine.server import parse_engine_options

            self.ledger.set_model(
                iid, parse_engine_options(config.options).model
            )
        except Exception:  # noqa: BLE001 — fake-kickoff tests use free-form options
            pass
        self.instances[iid] = instance
        published = dict(result)
        instance.last_revision = self._publish("CREATED", published)
        result["revision"] = instance.last_revision
        logger.info("created instance %s (rev %s)", iid, instance.last_revision)
        return result

    def _on_instance_stopped(self, instance_id: str, exitcode) -> None:
        """Sentinel callback: the child died on its own. Publishes STOPPED
        (wire behavior unchanged), then — when a restart policy is armed
        and the crash-loop budget allows — keeps the ChipLedger hold (the
        chips stay earmarked for the comeback; a concurrent create must
        not steal them) and schedules a supervised restart."""
        instance = self.instances.get(instance_id)
        if instance is None:
            return
        will_restart = self._restart_allowed(instance_id)
        if not will_restart:
            self.ledger.release(instance_id)
        obj = instance.get_status()
        obj["exit_code"] = exitcode
        instance.last_revision = self._publish("STOPPED", obj)
        logger.warning(
            "instance %s stopped itself (exit code %s)", instance_id, exitcode
        )
        if will_restart:
            try:
                import asyncio

                self._loop = asyncio.get_running_loop()
            except RuntimeError:
                pass
            self._schedule_restart(instance_id, exitcode)

    # -- crash supervision ---------------------------------------------------

    def _restart_allowed(self, instance_id: str) -> bool:
        pol = self.restart_policy
        if pol is None or pol.budget <= 0:
            return False
        if instance_id not in self.instances:
            return False
        with self._restart_lock:
            st = self._restart_states.setdefault(instance_id, _RestartState())
            now = time.monotonic()
            if (
                st.attempts
                and now - st.last_crash > pol.reset_window_s
            ):
                # survived a full window since the last crash: not a loop
                st.attempts = 0
            if st.attempts >= pol.budget:
                logger.error(
                    "instance %s crash-looped through its restart budget "
                    "(%d); leaving it stopped", instance_id, pol.budget,
                )
                return False
            return True

    def _schedule_restart(self, instance_id: str, exitcode) -> None:
        """Publish RESTARTING and arm the backoff timer for one attempt."""
        pol = self.restart_policy
        instance = self.instances.get(instance_id)
        if pol is None or instance is None:
            return
        with self._restart_lock:
            st = self._restart_states.setdefault(instance_id, _RestartState())
            attempt = st.attempts
            st.attempts += 1
            st.last_crash = time.monotonic()
            delay = min(pol.backoff_max_s, pol.backoff_s * (2 ** attempt))
            delay *= 1.0 + random.uniform(0.0, max(0.0, pol.jitter_frac))
            delay = min(delay, pol.backoff_max_s)  # cap is a hard ceiling
            timer = threading.Timer(
                delay,
                self._restart_instance,
                args=(instance_id, attempt + 1, st),
            )
            timer.daemon = True
            st.timer = timer
        obj = instance.get_status()
        obj.update(
            exit_code=exitcode,
            restart_attempt=attempt + 1,
            restart_budget=pol.budget,
            backoff_s=round(delay, 3),
        )
        instance.last_revision = self._publish("RESTARTING", obj)
        logger.warning(
            "instance %s: supervised restart %d/%d in %.2fs",
            instance_id, attempt + 1, pol.budget, delay,
        )
        timer.start()

    def _restart_instance(
        self, instance_id: str, attempt: int, st: _RestartState
    ) -> None:
        """Backoff-timer body: re-fork the child from the instance's
        CURRENT (engine-truth rewritten) options — a restarted instance
        comes back serving its last-swapped model — then reconcile the
        ChipLedger and re-arm crash detection.

        Runs under the restart lock end to end: Timer.cancel is a no-op
        once this body has started, so an explicit stop_instance racing it
        serializes on the lock instead — either the restart completes
        first (and the stop then stops the fresh child and releases the
        ledger), or the cancel lands first (``st.cancelled``) and no child
        is forked."""
        with self._restart_lock:
            if st.cancelled:
                return  # explicit stop won the race
            instance = self.instances.get(instance_id)
            if instance is None:
                return  # stopped/deleted while the backoff ran
            if instance.process is not None and instance.process.is_alive():
                return  # never restart a live child (manual intervention)
            try:
                with tracing.span(
                    "launcher.restart",
                    instance=instance_id,
                    attempt=attempt,
                ):
                    faults.fire("instance.spawn")
                    # append to the existing log: the crash forensics above
                    # the restart marker are exactly what the operator needs
                    instance.start(fresh_log=False, restart=True)
            except Exception as e:  # noqa: BLE001 — spawn failed: retry
                logger.warning(
                    "instance %s restart attempt %d failed to spawn: %s",
                    instance_id, attempt, e,
                )
                if self._restart_allowed(instance_id):
                    self._schedule_restart(instance_id, None)
                else:
                    self.ledger.release(instance_id)
                return
            # reconcile the ledger: the hold was kept across the crash
            # window; acquire is idempotent, and the model comes from the
            # rewritten options (what the child will actually serve)
            self.ledger.acquire(instance_id, instance.config.chip_ids)
            try:
                from ..engine.server import parse_engine_options

                self.ledger.set_model(
                    instance_id,
                    parse_engine_options(instance.config.options).model,
                )
            except Exception:  # noqa: BLE001 — free-form options
                pass
            obj = instance.get_status()
            obj["restart_attempt"] = attempt
            instance.last_revision = self._publish("RESTARTED", obj)
            logger.info(
                "instance %s restarted (attempt %d, pid %s)",
                instance_id, attempt,
                instance.process.pid if instance.process else None,
            )
        loop = self._loop
        if loop is not None and loop.is_running():
            # crash detection must be re-armed on the event loop thread
            loop.call_soon_threadsafe(self._rearm_sentinel, instance_id)

    def _rearm_sentinel(self, instance_id: str) -> None:
        instance = self.instances.get(instance_id)
        if instance is None:
            return
        try:
            instance.start_sentinel_watcher(self._on_instance_stopped)
        except RuntimeError:
            logger.warning(
                "no running loop; sentinel not re-armed for %s", instance_id
            )

    def _cancel_restart(self, instance_id: str) -> None:
        with self._restart_lock:
            st = self._restart_states.pop(instance_id, None)
            if st is not None:
                st.cancelled = True
                if st.timer is not None:
                    st.timer.cancel()

    def stop_instance(self, instance_id: str, timeout: float = 10) -> Dict[str, Any]:
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        instance = self.instances[instance_id]
        instance.cancel_sentinel_watcher()
        self._cancel_restart(instance_id)  # an explicit stop is not a crash
        result = instance.stop(timeout=timeout)
        del self.instances[instance_id]
        self.ledger.release(instance_id)
        published = dict(result)
        result["revision"] = self._publish("DELETED", published)
        logger.info("stopped instance %s", instance_id)
        return result

    def swap_instance(
        self,
        instance_id: str,
        model: str,
        checkpoint_dir: str = "",
        timeout: float = 300,
    ) -> Dict[str, Any]:
        """Traced entry for the launcher swap verb (the engine-side tree
        hangs off the launcher.rpc child span via traceparent)."""
        with tracing.span(
            "launcher.swap", instance=instance_id, model=model
        ):
            return self._swap_instance_impl(
                instance_id, model, checkpoint_dir, timeout
            )

    def _swap_instance_impl(
        self,
        instance_id: str,
        model: str,
        checkpoint_dir: str = "",
        timeout: float = 300,
    ) -> Dict[str, Any]:
        """Hot-swap the model a live instance serves: forward to the engine
        child's POST /v1/swap (no stop/start cycle — the chip set, the
        process, and its ChipLedger hold are all unchanged), then bring the
        stored config and ledger in line with the model actually served."""
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        instance = self.instances[instance_id]
        from ..engine.server import parse_engine_options

        try:
            previous = parse_engine_options(instance.config.options).model
        except Exception:
            previous = ""
        # The request id makes the verb safely recoverable: if the POST
        # times out with the swap possibly still executing, we do NOT
        # re-send (that could swap twice) — we poll GET /v1/swap and accept
        # the committed record carrying OUR id as the answer.
        request_id = uuidlib.uuid4().hex
        try:
            body = self._engine_request(
                instance_id, "POST", "/v1/swap",
                {
                    "model": model,
                    "checkpoint_dir": checkpoint_dir,
                    "request_id": request_id,
                },
                timeout, SwapFailed,
            )
        except SwapFailed as e:
            if e.status != 504:
                raise
            body = self._recover_swap_result(instance_id, request_id, e)
        from .instance import replace_model_option

        # rewrite from the ENGINE's answer, not the request: a pool hit
        # restores the pooled runtime's own checkpoint identity, and the
        # stored options must describe what the child actually serves
        # (a restart rebuilds from them)
        instance.config.options = replace_model_option(
            instance.config.options,
            model,
            checkpoint_dir=body.get("checkpoint_dir") or checkpoint_dir,
        )
        self.ledger.set_model(instance_id, model)
        self.ledger.set_pool(instance_id, body.get("pool"))
        self.ledger.set_quant(instance_id, body.get("quant"))
        obj = instance.get_status()
        obj["swap"] = body
        instance.last_revision = self._publish("SWAPPED", obj)
        logger.info(
            "swapped instance %s: %s -> %s (pool_hit=%s, rev %s)",
            instance_id, previous, model, body.get("pool_hit"),
            instance.last_revision,
        )
        return {
            "instance_id": instance_id,
            "model": model,
            "previous_model": previous,
            "swap": body,
            "revision": instance.last_revision,
        }

    def _recover_swap_result(
        self,
        instance_id: str,
        request_id: str,
        timeout_exc: "SwapFailed",
        window_s: float = 10.0,
        poll_s: float = 0.5,
    ) -> Dict[str, Any]:
        """Timed-out swap recovery: poll the engine's committed-swap record
        for our request id. Found => the swap happened exactly once and
        this is its result; not found within the window => surface the
        original timeout as a 504 (the caller knows the verb may still be
        executing and can widen its timeout)."""
        deadline = time.monotonic() + window_s
        while time.monotonic() < deadline:
            try:
                body = self._engine_request(
                    instance_id, "GET", "/v1/swap", None,
                    min(5.0, window_s), SwapFailed, retries=1,
                )
            except SwapFailed:
                body = {}
            if body.get("request_id") == request_id:
                logger.info(
                    "swap on instance %s recovered via request id after a "
                    "timeout", instance_id,
                )
                return body
            time.sleep(poll_s)
        raise SwapFailed(
            instance_id, 504,
            f"swap timed out and no committed record with request id "
            f"{request_id} appeared within {window_s}s "
            f"({timeout_exc.detail})",
        )

    @staticmethod
    def _is_connection_refused(e: BaseException) -> bool:
        if isinstance(e, (ConnectionRefusedError, faults.FaultError)):
            # an injected launcher.rpc fault models exactly this class of
            # failure: the request never reached the engine
            return True
        if isinstance(e, urllib.error.URLError):
            return isinstance(
                getattr(e, "reason", None), ConnectionRefusedError
            )
        return False

    @staticmethod
    def _is_timeout(e: BaseException) -> bool:
        if isinstance(e, (TimeoutError, socket.timeout)):
            return True
        if isinstance(e, urllib.error.URLError):
            return isinstance(
                getattr(e, "reason", None), (TimeoutError, socket.timeout)
            )
        return False

    def _engine_request(
        self,
        instance_id: str,
        method: str,
        api_path: str,
        body: Optional[Dict[str, Any]],
        timeout: float,
        exc_cls,
        retries: int = 2,
        retry_backoff_s: float = 0.2,
    ) -> Dict[str, Any]:
        """Forward an admin verb to a live instance's engine child; maps
        stored-options/HTTP failures onto `exc_cls(instance_id, status,
        detail)` the REST layer turns into 4xx/502/503.

        Connection-refused is retried up to ``retries`` times with
        exponential backoff + jitter: refused means the request never
        reached the engine (crash window mid-restart, child not yet bound),
        so a retry is safe for EVERY verb. A TIMEOUT is never retried here
        — the request may be executing (a timed-out swap re-sent blindly
        could swap twice); it raises with status **504** (vs 502 for
        unreachable) so callers with an idempotent recovery path
        (swap_instance's request-id replay) can take it."""
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        instance = self.instances[instance_id]
        from ..engine.server import parse_engine_options

        try:
            opts = parse_engine_options(instance.config.options)
        except Exception as e:
            # free-form options are tolerated at create time (fake-kickoff
            # managers); admin verbs on such an instance are a client error
            raise exc_cls(
                instance_id, 400,
                f"stored options are not engine options: {e}",
            )
        verb = f"{method} {api_path}"
        # The RPC span: the engine-side handler adopts the traceparent we
        # send, so the child's swap/sleep tree hangs off this span in one
        # coherent trace across the process boundary (docs/tracing.md).
        rpc_sp = tracing.begin("launcher.rpc", instance=instance_id, verb=verb)
        req = urllib.request.Request(
            f"http://127.0.0.1:{opts.port}{api_path}",
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method=method,
        )
        tp = rpc_sp.traceparent()
        if tp:
            req.add_header("Traceparent", tp)
        attempt = 0
        while True:
            t0 = time.monotonic()
            try:
                faults.fire("launcher.rpc")
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    out = json.loads(resp.read() or b"{}")
                LAUNCHER_RPC_SECONDS.labels(
                    verb=verb, outcome="ok"
                ).observe(time.monotonic() - t0)
                rpc_sp.set(outcome="ok", attempts=attempt + 1)
                rpc_sp.end()
                return out
            except urllib.error.HTTPError as e:
                detail = e.read().decode(errors="replace")[:500]
                LAUNCHER_RPC_SECONDS.labels(
                    verb=verb, outcome=f"http_{e.code}"
                ).observe(time.monotonic() - t0)
                rpc_sp.set(outcome=f"http_{e.code}")
                rpc_sp.end()
                raise exc_cls(instance_id, e.code, detail)
            except Exception as e:  # noqa: BLE001 — refused, timeout, ...
                if self._is_connection_refused(e) and attempt < retries:
                    LAUNCHER_RPC_SECONDS.labels(
                        verb=verb, outcome="refused"
                    ).observe(time.monotonic() - t0)
                    attempt += 1
                    delay = retry_backoff_s * (2 ** (attempt - 1))
                    delay *= 1.0 + random.random()  # jitter: no lockstep
                    logger.warning(
                        "engine %s refused %s %s (attempt %d/%d); "
                        "retrying in %.2fs",
                        instance_id, method, api_path, attempt, retries,
                        delay,
                    )
                    time.sleep(min(delay, 2.0))
                    continue
                if self._is_timeout(e):
                    LAUNCHER_RPC_SECONDS.labels(
                        verb=verb, outcome="timeout"
                    ).observe(time.monotonic() - t0)
                    rpc_sp.set(outcome="timeout")
                    rpc_sp.end()
                    raise exc_cls(instance_id, 504, f"engine timed out: {e}")
                LAUNCHER_RPC_SECONDS.labels(
                    verb=verb, outcome="unreachable"
                ).observe(time.monotonic() - t0)
                rpc_sp.set(outcome="unreachable")
                rpc_sp.end()
                raise exc_cls(instance_id, 502, f"engine unreachable: {e}")

    def prefetch_instance(
        self,
        instance_id: str,
        model: str,
        checkpoint_dir: str = "",
        timeout: float = 60,
    ) -> Dict[str, Any]:
        """Background-prefetch verb: have a live instance stage `model`'s
        weights host-resident (engine POST /v1/prefetch) while it keeps
        serving its current model, and record the hint in the ChipLedger —
        the dual-pods controller's way of warming the predicted next swap
        without touching the chip set or the serving process."""
        body = self._engine_request(
            instance_id, "POST", "/v1/prefetch",
            {"model": model, "checkpoint_dir": checkpoint_dir},
            timeout, PrefetchFailed,
        )
        # The hint is ADVISORY: it is recorded when the engine accepts the
        # staging and the background outcome is reconciled on status reads
        # (get_instance_prefetch drops it on failed/rejected/aborted) — a
        # controller that acts on the hint without having polled may still
        # get a cold build if the staging later failed.
        self.ledger.set_prefetched(instance_id, model)
        self.ledger.set_pool(instance_id, body.get("pool"))
        logger.info(
            "prefetch on instance %s: %s (state=%s)",
            instance_id, model, body.get("state"),
        )
        return {
            "instance_id": instance_id,
            "model": model,
            "prefetch": body,
        }

    def abort_instance_prefetch(
        self, instance_id: str, timeout: float = 90
    ) -> Dict[str, Any]:
        """Cancel an instance's in-flight prefetch (engine DELETE
        /v1/prefetch) and drop the ledger hint."""
        body = self._engine_request(
            instance_id, "DELETE", "/v1/prefetch", None, timeout,
            PrefetchFailed,
        )
        # keep the hint when there was nothing to abort because the
        # prefetch already COMPLETED: the staged weights are still pooled
        # and a swap to them is still warm — the hint is still true
        if body.get("aborted") or body.get("state") != "completed":
            self.ledger.set_prefetched(instance_id, None)
        return {
            "instance_id": instance_id,
            "prefetch": body,
        }

    def get_instance_prefetch(
        self, instance_id: str, timeout: float = 10
    ) -> Dict[str, Any]:
        """Prefetch status passthrough (engine GET /v1/prefetch). Also
        reconciles the advisory ledger hint: a staging that ended
        failed/rejected/aborted is no longer a warm next model."""
        body = self._engine_request(
            instance_id, "GET", "/v1/prefetch", None, timeout, PrefetchFailed
        )
        if body.get("state") in ("failed", "rejected", "aborted"):
            self.ledger.set_prefetched(instance_id, None)
        return {"instance_id": instance_id, "prefetch": body}

    # -- live request migration / node drain ---------------------------------

    def _parsed_opts(self, instance_id: str):
        """Parsed engine options of a live instance's stored config, or
        None when the options are free-form (fake-kickoff managers)."""
        from ..engine.server import parse_engine_options

        try:
            return parse_engine_options(
                self.instances[instance_id].config.options
            )
        except Exception:
            return None

    def _resolve_migration_dest(
        self, instance_id: str, model: str, dest_id: Optional[str]
    ) -> str:
        """Pick (or validate) the sibling instance a migration lands on.
        Eligibility here is only 'another live instance whose stored
        options serve the same model' — the engines themselves enforce
        the real identity gate (weight fingerprint / checkpoint path +
        page geometry) at import time."""
        if dest_id is not None:
            if dest_id == instance_id:
                raise MigrateFailed(
                    instance_id, 400,
                    "destination must be a different instance",
                )
            if dest_id not in self.instances:
                raise MigrateFailed(
                    instance_id, 404,
                    f"no such destination instance {dest_id}",
                )
            opts = self._parsed_opts(dest_id)
            if opts is None or opts.model != model:
                raise MigrateFailed(
                    instance_id, 409,
                    f"destination {dest_id} does not serve {model!r}; "
                    "migration needs a sibling with provable weight "
                    "identity",
                )
            return dest_id
        for other in self.instances:
            if other == instance_id:
                continue
            opts = self._parsed_opts(other)
            if opts is not None and opts.model == model:
                return other
        raise MigrateFailed(
            instance_id, 409,
            f"no sibling instance serves {model!r}; nothing to migrate to",
        )

    def _abort_migration_on_source(
        self, instance_id: str, token: str, timeout: float
    ) -> None:
        """Best-effort fenced abort after a failed import: the source
        resumes the parked bundle locally. A failure here is logged, not
        raised — the import failure stays the primary error, and the
        bundle remains fenced on the source for a later manual abort."""
        if not token:
            return
        try:
            self._engine_request(
                instance_id, "POST", "/v1/parked/abort",
                {"fence_token": token}, timeout, MigrateFailed,
            )
        except (MigrateFailed, KeyError) as e:
            logger.error(
                "migration abort on source %s failed (%s); the bundle "
                "stays fenced under token %s — POST /v1/parked/abort "
                "when the engine is reachable again",
                instance_id, e, token,
            )

    def migrate_instance(
        self,
        instance_id: str,
        dest_id: Optional[str] = None,
        timeout: float = 300,
    ) -> Dict[str, Any]:
        """Traced entry for the live-migration verb (docs/launcher.md)."""
        with tracing.span(
            "launcher.migrate", instance=instance_id, dest=dest_id or ""
        ):
            return self._migrate_instance_impl(instance_id, dest_id, timeout)

    def _migrate_instance_impl(
        self,
        instance_id: str,
        dest_id: Optional[str],
        timeout: float,
    ) -> Dict[str, Any]:
        """Transactional handoff of an instance's live work to a sibling
        serving the same model: export the fenced bundle (engine GET
        /v1/parked/{model}), import it on the destination (POST
        /v1/parked), release the source (POST /v1/parked/release) so it
        proxies every surviving stream to the destination's claims.

        Failure discipline mirrors the engine's drilled recoveries:

        * export failure — the bundle never left the source; the engine
          already resumed it locally, we just surface the error;
        * import refusal (409/400) or import timeout (504, never
          re-sent) — abort the fence so the source resumes locally;
        * import failure (5xx/502) — ONE blind retry: the fence makes it
          idempotent (a seated import replays its stored ack, a rolled-
          back one seats fresh); a second failure aborts back to the
          source.
        """
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        opts = self._parsed_opts(instance_id)
        if opts is None:
            raise MigrateFailed(
                instance_id, 400,
                "stored options are not engine options",
            )
        model = opts.model
        dest = self._resolve_migration_dest(instance_id, model, dest_id)
        doc = self._engine_request(
            instance_id, "GET", f"/v1/parked/{model}", None, timeout,
            MigrateFailed,
        )
        token = str((doc.get("fence") or {}).get("token") or "")
        try:
            ack = self._engine_request(
                dest, "POST", "/v1/parked", doc, timeout, MigrateFailed,
            )
        except MigrateFailed as e:
            if e.status in (400, 409, 504):
                # refusal (nothing displaced) or timeout (may still be
                # executing — never re-send): resume on the source
                self._abort_migration_on_source(instance_id, token, timeout)
                raise
            try:
                ack = self._engine_request(
                    dest, "POST", "/v1/parked", doc, timeout,
                    MigrateFailed,
                )
            except MigrateFailed:
                self._abort_migration_on_source(instance_id, token, timeout)
                raise
        dest_opts = self._parsed_opts(dest)
        dest_url = f"http://127.0.0.1:{dest_opts.port}" if dest_opts else ""
        rel = self._engine_request(
            instance_id, "POST", "/v1/parked/release",
            {
                "fence_token": token,
                "dest": dest_url,
                "claims": ack.get("claims") or {},
            },
            timeout, MigrateFailed,
        )
        result = {
            "instance_id": instance_id,
            "dest_id": dest,
            "model": model,
            "fence_token": token,
            "requests": int(ack.get("requests", 0)),
            "migrated": int(rel.get("migrated", 0)),
            "proxied": int(rel.get("proxied", 0)),
            "bytes": int(doc.get("nbytes", 0)),
            "import": {k: v for k, v in ack.items() if k != "claims"},
            "release": rel,
        }
        obj = self.instances[instance_id].get_status()
        obj["migration"] = {
            k: result[k]
            for k in (
                "dest_id", "model", "fence_token", "requests", "migrated",
                "proxied", "bytes",
            )
        }
        result["revision"] = self._publish("MIGRATED", obj)
        logger.info(
            "migrated instance %s -> %s: %d request(s), %d byte(s), "
            "%d stream(s) proxied (rev %s)",
            instance_id, dest, result["requests"], result["bytes"],
            result["proxied"], result["revision"],
        )
        return result

    def drain_instance(
        self,
        instance_id: str,
        timeout: float = 300,
        max_passes: int = 8,
    ) -> Dict[str, Any]:
        """Traced entry for the node-drain verb (docs/operations.md
        "Draining a node without dropping streams")."""
        with tracing.span("launcher.drain", instance=instance_id):
            return self._drain_instance_impl(instance_id, timeout, max_passes)

    def _drain_instance_impl(
        self, instance_id: str, timeout: float, max_passes: int
    ) -> Dict[str, Any]:
        """Repeat migrate passes until the instance reports no queued or
        in-flight work, then declare it drained: every displaced stream
        keeps flowing through the source's claim proxies, new arrivals
        between passes are caught by the next pass, and the instance is
        left idle — safe to stop or kill. Streams still mid-proxy do not
        count as work: the source only forwards tokens for them."""
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        passes: List[Dict[str, Any]] = []
        drained = False
        depth = 0
        for _ in range(max_passes + 1):
            try:
                stats = self._poll_instance_stats(
                    instance_id, min(timeout, 10.0)
                )
            except (StatsFailed, KeyError) as e:
                raise DrainFailed(
                    instance_id, 502, f"stats poll failed: {e}"
                )
            depth = int(stats.get("queue_depth", 0))
            if depth == 0:
                drained = True
                break
            if len(passes) >= max_passes:
                break
            try:
                res = self.migrate_instance(instance_id, timeout=timeout)
            except MigrateFailed as e:
                if e.status == 409 and len(passes) + 1 < max_passes:
                    # a refused pass displaced nothing (the source
                    # resumed or kept its streams): a busy sibling may
                    # free slot/page capacity by the next pass
                    passes.append({"refused": e.detail[:200]})
                    time.sleep(0.2)
                    continue
                raise DrainFailed(
                    instance_id, e.status,
                    f"migrate pass {len(passes) + 1} failed: {e.detail}",
                )
            passes.append({
                "dest_id": res["dest_id"],
                "requests": res["requests"],
                "migrated": res["migrated"],
                "bytes": res["bytes"],
            })
        if not drained:
            raise DrainFailed(
                instance_id, 409,
                f"{depth} request(s) still live after {len(passes)} "
                "migrate pass(es); arrival rate may exceed drain rate — "
                "stop routing new work to this instance and retry",
            )
        result = {
            "instance_id": instance_id,
            "drained": True,
            "passes": passes,
            "migrated": sum(p.get("migrated", 0) for p in passes),
            "bytes": sum(p.get("bytes", 0) for p in passes),
        }
        obj = self.instances[instance_id].get_status()
        obj["drain"] = {
            "passes": len(passes),
            "migrated": result["migrated"],
            "bytes": result["bytes"],
        }
        result["revision"] = self._publish("DRAINED", obj)
        logger.info(
            "drained instance %s: %d pass(es), %d stream(s) migrated "
            "(rev %s)",
            instance_id, len(passes), result["migrated"],
            result["revision"],
        )
        return result

    def _poll_instance_stats(
        self, instance_id: str, timeout: float
    ) -> Dict[str, Any]:
        return self._engine_request(
            instance_id, "GET", "/v1/stats", None, timeout, StatsFailed,
            retries=0,
        )

    def fleet_rollup(
        self, timeout: float = 1.5, ttl_s: float = 1.0
    ) -> Dict[str, Any]:
        """Aggregate every live engine child's GET /v1/stats into the
        node-level SLO/goodput view (the ``fleet`` block of GET
        /v2/vllm/instances) and mirror the aggregates onto the
        fma_launcher_fleet_* gauges. Children are polled concurrently
        with a short per-poll timeout and no retries: an unreachable or
        free-form-options instance degrades to an ``unreachable`` row,
        never an error for the whole read."""
        now = time.monotonic()
        with self._fleet_lock:
            cached = self._fleet_cache
            if cached is not None and now - cached[0] < ttl_s:
                return cached[1]
            ids = list(self.instances)
        # Poll OUTSIDE the lock: a degraded fleet (several unreachable
        # children timing out) must slow only this refresher, not every
        # concurrent /metrics scrape queued behind the lock. Two cold
        # readers may both poll; the second write just wins the cache.
        per_instance: Dict[str, Dict[str, Any]] = {}
        if ids:
            import concurrent.futures as _cf

            with _cf.ThreadPoolExecutor(
                max_workers=min(8, len(ids))
            ) as pool:
                futs = {
                    iid: pool.submit(
                        self._poll_instance_stats, iid, timeout
                    )
                    for iid in ids
                }
            for iid, fut in futs.items():
                try:
                    stats = fut.result()
                except (StatsFailed, KeyError) as e:
                    per_instance[iid] = {
                        "reporting": False,
                        "error": str(e)[:200],
                    }
                    continue
                per_instance[iid] = {"reporting": True, **stats}
        met = violated = 0
        queue_depth = 0
        arrival = 0.0
        goodput = generated = finished = 0
        actuations = 0
        actuations_per_hour = 0.0
        aborted: Dict[str, int] = {}
        preempted = resumed = zd_aborted = zd_migrated = 0
        parked_kv_bytes = 0
        mig: Dict[str, int] = {
            "committed": 0, "resumed_local": 0, "state_loss": 0,
            "requests_out": 0, "requests_in": 0,
            "bytes_out": 0, "bytes_in": 0,
        }
        slo_exemplars: List[Dict[str, Any]] = []
        reporting = 0
        for iid, row in per_instance.items():
            if not row.get("reporting"):
                continue
            reporting += 1
            for ex in row.get("slo_exemplars") or []:
                if isinstance(ex, dict):
                    slo_exemplars.append({"instance": iid, **ex})
            slo = row.get("slo") or {}
            met += int(slo.get("met", 0))
            violated += int(slo.get("violated", 0))
            queue_depth += int(row.get("queue_depth", 0))
            arrival += float(row.get("arrival_rate_rps", 0.0))
            goodput += int(row.get("goodput_tokens", 0))
            generated += int(row.get("generated_tokens", 0))
            finished += int(row.get("finished_requests", 0))
            acts = sum(
                int(v) for v in (row.get("actuations") or {}).values()
            )
            actuations += acts
            uptime = float(row.get("uptime_s", 0.0))
            if uptime > 0:
                actuations_per_hour += acts * 3600.0 / uptime
            for cause, n in (row.get("aborted") or {}).items():
                aborted[cause] = aborted.get(cause, 0) + int(n)
            zd = row.get("zero_drain") or {}
            preempted += int(zd.get("preempted", 0))
            resumed += int(zd.get("resumed", 0))
            zd_aborted += int(zd.get("aborted", 0))
            zd_migrated += int(zd.get("migrated", 0))
            parked_kv_bytes += int(zd.get("parked_kv_bytes", 0))
            mg = row.get("migration") or {}
            for k in mig:
                mig[k] += int(mg.get(k, 0))
        judged = met + violated
        attainment = round(met / judged, 6) if judged else None
        fleet = {
            "instances_total": len(ids),
            "instances_reporting": reporting,
            "queue_depth": queue_depth,
            "arrival_rate_rps": round(arrival, 6),
            "slo_requests_met": met,
            "slo_requests_violated": violated,
            "slo_attainment": attainment,
            "finished_requests": finished,
            "generated_tokens": generated,
            "goodput_tokens": goodput,
            "actuations": actuations,
            "actuations_per_hour": round(actuations_per_hour, 3),
            "aborted": aborted,
            # zero-drain preemption rollup (engine /v1/stats zero_drain):
            # fleet-wide "did actuation drop any stream" in one read
            "zero_drain": {
                "preempted": preempted,
                "resumed": resumed,
                "aborted": zd_aborted,
                "migrated": zd_migrated,
                "parked_kv_bytes": parked_kv_bytes,
            },
            # live-migration rollup (engine /v1/stats migration):
            # fleet-wide "did any handoff lose state" in one read
            "migration": mig,
            # SLO-violation exemplars lifted from every reporting child
            # (engine /v1/stats slo_exemplars), each tagged with the
            # instance it came from so an operator can pull the trace
            # via that child's GET /v1/traces?trace_id=
            "slo_exemplars": slo_exemplars[-16:],
            "per_instance": per_instance,
        }
        LAUNCHER_FLEET_INSTANCES.labels(state="reporting").set(reporting)
        LAUNCHER_FLEET_INSTANCES.labels(state="unreachable").set(
            len(ids) - reporting
        )
        LAUNCHER_FLEET_QUEUE_DEPTH.set(queue_depth)
        LAUNCHER_FLEET_ARRIVAL_RATE.set(arrival)
        LAUNCHER_FLEET_SLO_ATTAINMENT.set(
            attainment if attainment is not None else 1.0
        )
        LAUNCHER_FLEET_GOODPUT_TOKENS.set(goodput)
        LAUNCHER_FLEET_ACTUATIONS_PER_HOUR.set(actuations_per_hour)
        with self._fleet_lock:
            self._fleet_cache = (time.monotonic(), fleet)
        return fleet

    def stop_all_instances(self, timeout: float = 10) -> Dict[str, Any]:
        stopped = []
        for iid in list(self.instances):
            self.stop_instance(iid, timeout=timeout)
            stopped.append(iid)
        return {"status": "all_stopped", "stopped_instances": stopped}

    def get_instance_status(self, instance_id: str) -> Dict[str, Any]:
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        return self.instances[instance_id].get_status()

    def get_all_instances_status(
        self, include_fleet: bool = False
    ) -> Dict[str, Any]:
        statuses = []
        running = 0
        for instance in self.instances.values():
            st = instance.get_status()
            statuses.append(st)
            if st["status"] == STATUS_RUNNING:
                running += 1
        out: Dict[str, Any] = {
            "total_instances": len(statuses),
            "running_instances": running,
            "instances": statuses,
            # node-local actuation state a multi-model scheduler reads in
            # one call: who holds which chips, what each holder serves,
            # what's staged (prefetch hints), and each holder's tiered
            # pool shape (pooled models, deduped residency, disk tier)
            "ledger": {
                "models": self.ledger.models(),
                "prefetched": self.ledger.prefetched(),
                "pools": self.ledger.pools(),
                # per-holder transfer mode of the last swap ("int8"/"fp8"
                # when the holder actuates compressed, docs/perf.md)
                "quant": self.ledger.quants(),
            },
        }
        if include_fleet:
            # blocking child polls: only REST's executor-threaded GET
            # /v2/vllm/instances asks for it — in-process callers on the
            # event loop (the notifier's lister) must not
            try:
                out["fleet"] = self.fleet_rollup()
            except Exception as e:  # noqa: BLE001 — rollup never fails the read
                logger.warning("fleet rollup failed: %s", e)
                out["fleet"] = {"error": str(e)[:200]}
            # cost-oracle rollup (docs/launcher.md "The costs block"):
            # each reporting child's /v1/stats already carries its
            # bandwidth EWMAs + prediction accuracy — lift them into the
            # ledger so ONE detailed read serves the scheduler's whole
            # input: demand (fleet), state (ledger), cost (this block),
            # all from the same poll cycle
            per = (out["fleet"] or {}).get("per_instance") or {}
            out["ledger"]["costs"] = {
                iid: row.get("costs")
                for iid, row in per.items()
                if row.get("reporting") and row.get("costs") is not None
            }
        return out

    def list_instances(self) -> List[str]:
        return list(self.instances.keys())

    def get_instance_log_bytes(
        self, instance_id: str, start: int = 0, end: Optional[int] = None
    ):
        if instance_id not in self.instances:
            raise KeyError(instance_id)
        return self.instances[instance_id].get_log_bytes(start, end)
