"""Launcher REST API (aiohttp).

Wire-compatible with the reference launcher's FastAPI surface
(launcher.py:568-800) so the reference's Go `launcherclient` drives this
launcher unchanged: same paths (`/v2/vllm/instances...`), same status codes
(201 create, 409 duplicate PUT, 404 missing, 410 stale watch revision, 206/416
ranged logs with Content-Range), same NDJSON watch event shape
``{"type": CREATED|STOPPED|DELETED, "object": {...}}``.
"""

from __future__ import annotations

import asyncio
import json
import logging
import re
from http import HTTPStatus
from typing import Optional, Tuple

from aiohttp import web

from ..utils import tracing
from ..utils.events import RevisionTooOld
from .instance import InstanceConfig, InvalidInstanceConfig, LogRangeNotAvailable
from .manager import ChipConflict
from .manager import DrainFailed
from .manager import EngineProcessManager
from .manager import MigrateFailed
from .manager import PrefetchFailed
from .manager import SwapFailed

logger = logging.getLogger(__name__)

_RANGE_RE = re.compile(r"^bytes=(\d+)-(\d+)?$")


def parse_range_header(value: str) -> Tuple[int, Optional[int]]:
    """``bytes=START-END`` or ``bytes=START-`` (suffix ranges rejected)."""
    m = _RANGE_RE.match(value)
    if m is None:
        raise ValueError(f"Unsupported or malformed Range header: {value}")
    start = int(m.group(1))
    end = int(m.group(2)) if m.group(2) else None
    if end is not None and end < start:
        raise ValueError(f"Range end ({end}) must be >= start ({start})")
    return start, end


def build_app(manager: EngineProcessManager) -> web.Application:
    app = web.Application()
    app["manager"] = manager

    def _traced_call(request: web.Request, fn):
        """Run a blocking manager verb on the executor with the caller's
        ``traceparent`` (if any) as the current context — the launcher's
        create/swap spans then join the controller's actuation trace
        (docs/tracing.md), and the engine hop + fork env carry it on."""
        return tracing.run_traced(
            asyncio.get_running_loop(), request.headers, fn
        )

    async def health(request: web.Request) -> web.Response:
        return web.json_response({"status": "OK"})

    async def metrics(request: web.Request) -> web.Response:
        """Launcher-process prometheus exposition: the launcher RPC
        latency family (fma_launcher_rpc_seconds) lives in THIS process —
        without this route it would be registered but unscrapeable. The
        fleet rollup refreshes first (executor: it polls engine children
        over HTTP) so one scrape carries current fma_launcher_fleet_*
        aggregates."""
        from prometheus_client import generate_latest

        loop = asyncio.get_running_loop()
        try:
            await loop.run_in_executor(None, manager.fleet_rollup)
        except Exception:  # noqa: BLE001 — stale gauges beat a failed scrape
            logger.warning("fleet rollup during scrape failed", exc_info=True)
        return web.Response(
            body=generate_latest(), content_type="text/plain"
        )

    async def index(request: web.Request) -> web.Response:
        return web.json_response(
            {
                "name": "Multi-Instance Engine Management API (TPU)",
                "version": "2.0",
                # what this launcher enumerated and how ("real:pci+vfio",
                # "naive-mock", ...): the ids a create's gpu_uuids may name
                "chips": {
                    "mode": manager.translator.mode,
                    "ids": manager.translator.chip_ids(),
                },
                "endpoints": {
                    "index": "GET /",
                    "health": "GET /health",
                    "metrics": "GET /metrics",
                    "create_instance": "POST /v2/vllm/instances",
                    "create_named_instance": "PUT /v2/vllm/instances/{instance_id}",
                    "delete_instance": "DELETE /v2/vllm/instances/{instance_id}",
                    "delete_all_instances": "DELETE /v2/vllm/instances",
                    "get_instance_status": "GET /v2/vllm/instances/{instance_id}",
                    "get_all_instances": "GET /v2/vllm/instances",
                    "get_instance_logs": "GET /v2/vllm/instances/{instance_id}/log",
                    "swap_instance": "POST /v2/vllm/instances/{instance_id}/swap",
                    "prefetch_instance": "POST /v2/vllm/instances/{instance_id}/prefetch",
                    "prefetch_status": "GET /v2/vllm/instances/{instance_id}/prefetch",
                    "abort_prefetch": "DELETE /v2/vllm/instances/{instance_id}/prefetch",
                    "migrate_instance": "POST /v2/vllm/instances/{instance_id}/migrate",
                    "drain_instance": "POST /v2/vllm/instances/{instance_id}/drain",
                    "watch_instances": "GET /v2/vllm/instances/watch",
                    "faults": "GET/POST/DELETE /v2/vllm/faults",
                    "traces": "GET /v2/vllm/traces",
                    "exemplars": "GET /v2/vllm/exemplars",
                },
            }
        )

    async def _parse_config(request: web.Request) -> InstanceConfig:
        try:
            body = await request.json()
            return InstanceConfig.from_dict(body)
        except (json.JSONDecodeError, ValueError, TypeError) as e:
            raise web.HTTPUnprocessableEntity(text=f"invalid instance config: {e}")

    async def create_instance(request: web.Request) -> web.Response:
        config = await _parse_config(request)
        try:
            # create forks + may probe overlapping engines over HTTP (2 s
            # timeout each) — keep the event loop free
            result = await _traced_call(
                request, lambda: manager.create_instance(config)
            )
        except InvalidInstanceConfig as e:
            raise web.HTTPUnprocessableEntity(text=str(e))
        except ChipConflict as e:
            raise web.HTTPConflict(text=str(e))
        except Exception as e:
            logger.exception("create failed")
            raise web.HTTPInternalServerError(text=str(e))
        _watch_sentinel(manager, result["instance_id"])
        return web.json_response(result, status=HTTPStatus.CREATED)

    async def create_named_instance(request: web.Request) -> web.Response:
        instance_id = request.match_info["instance_id"]
        config = await _parse_config(request)
        try:
            result = await _traced_call(
                request,
                lambda: manager.create_instance(config, instance_id=instance_id),
            )
        except InvalidInstanceConfig as e:
            raise web.HTTPUnprocessableEntity(text=str(e))
        except (ValueError, ChipConflict) as e:
            raise web.HTTPConflict(text=str(e))
        except Exception as e:
            logger.exception("create failed")
            raise web.HTTPInternalServerError(text=str(e))
        _watch_sentinel(manager, instance_id)
        return web.json_response(result, status=HTTPStatus.CREATED)

    async def delete_instance(request: web.Request) -> web.Response:
        instance_id = request.match_info["instance_id"]
        loop = asyncio.get_running_loop()
        inst = manager.instances.get(instance_id)
        if inst is not None:
            inst.cancel_sentinel_watcher()  # must run on the loop thread
        try:
            # stop() blocks on SIGTERM/join for seconds; keep the loop live.
            result = await loop.run_in_executor(
                None, manager.stop_instance, instance_id
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        return web.json_response(result)

    async def delete_all(request: web.Request) -> web.Response:
        loop = asyncio.get_running_loop()
        for inst in list(manager.instances.values()):
            inst.cancel_sentinel_watcher()
        result = await loop.run_in_executor(None, manager.stop_all_instances)
        return web.json_response(result)

    async def get_all(request: web.Request) -> web.Response:
        detail = request.query.get("detail", "true").lower() != "false"
        if detail:
            # executor: the fleet block polls engine children over HTTP
            # (short per-child timeout); the loop must stay free
            return web.json_response(
                await asyncio.get_running_loop().run_in_executor(
                    None,
                    lambda: manager.get_all_instances_status(
                        include_fleet=True
                    ),
                )
            )
        ids = manager.list_instances()
        return web.json_response(
            {"revision": manager.revision, "instance_ids": ids, "count": len(ids)}
        )

    async def get_one(request: web.Request) -> web.Response:
        instance_id = request.match_info["instance_id"]
        try:
            return web.json_response(manager.get_instance_status(instance_id))
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")

    async def watch(request: web.Request) -> web.StreamResponse:
        since_raw = request.query.get("since")
        try:
            since = int(since_raw) if since_raw is not None else None
        except ValueError:
            raise web.HTTPBadRequest(text=f"invalid since revision: {since_raw!r}")
        if since is not None:
            oldest = manager.broadcaster.oldest_revision
            if oldest is not None and since < oldest - 1:
                raise web.HTTPGone(
                    text=f"Requested revision {since} is no longer available. "
                    f"Oldest available: {oldest}."
                )
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "application/x-ndjson",
                "X-Content-Type-Options": "nosniff",
            },
        )
        await resp.prepare(request)

        async def send(obj) -> None:
            await resp.write((json.dumps(obj) + "\n").encode())

        if since is None:
            start_revision = manager.revision
            for instance in list(manager.instances.values()):
                await send({"type": "CREATED", "object": instance.get_status()})
        else:
            start_revision = since
        try:
            async for event in manager.broadcaster.subscribe(start_revision):
                await send(event)
        except RevisionTooOld:
            pass
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        return resp

    async def swap_instance(request: web.Request) -> web.Response:
        """Model hot-swap verb: rebind a live instance to a different model
        over the engine child's /v1/swap — same chip set, same process, no
        stop/start cycle (docs/engine.md "Model hot-swap")."""
        instance_id = request.match_info["instance_id"]
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str) or not model:
            raise web.HTTPUnprocessableEntity(
                text="swap requires a 'model' string"
            )
        checkpoint_dir = body.get("checkpoint_dir") or ""
        if not isinstance(checkpoint_dir, str):
            raise web.HTTPUnprocessableEntity(
                text="checkpoint_dir must be a string"
            )
        try:
            # the swap streams model state for seconds; keep the loop free
            result = await _traced_call(
                request,
                lambda: manager.swap_instance(
                    instance_id, model, checkpoint_dir=checkpoint_dir
                ),
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except SwapFailed as e:
            # engine-side rejection (bad model name, gang, sleeping) maps
            # to the client's fault; a rolled-back swap is a retryable 503;
            # a timed-out-and-unrecovered swap is 504; an unreachable child
            # is a gateway error
            if 400 <= e.status < 500:
                raise web.HTTPBadRequest(text=str(e))
            if e.status == 503:
                raise web.HTTPServiceUnavailable(text=str(e))
            if e.status == 504:
                raise web.HTTPGatewayTimeout(text=str(e))
            raise web.HTTPBadGateway(text=str(e))
        return web.json_response(result)

    def _map_prefetch_error(e: PrefetchFailed):
        # engine-side rejection (bad model, gang, already running) is the
        # client's fault; a timed-out child is 504, unreachable is 502
        if 400 <= e.status < 500:
            return web.HTTPBadRequest(text=str(e))
        if e.status == 504:
            return web.HTTPGatewayTimeout(text=str(e))
        return web.HTTPBadGateway(text=str(e))

    async def prefetch_instance(request: web.Request) -> web.Response:
        """Background-prefetch verb: stage a model's weights host-resident
        on a live instance (engine POST /v1/prefetch) while it keeps
        serving — the controller's hint for the predicted next swap."""
        instance_id = request.match_info["instance_id"]
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str) or not model:
            raise web.HTTPUnprocessableEntity(
                text="prefetch requires a 'model' string"
            )
        checkpoint_dir = body.get("checkpoint_dir") or ""
        if not isinstance(checkpoint_dir, str):
            raise web.HTTPUnprocessableEntity(
                text="checkpoint_dir must be a string"
            )
        try:
            result = await _traced_call(
                request,
                lambda: manager.prefetch_instance(
                    instance_id, model, checkpoint_dir=checkpoint_dir
                ),
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except PrefetchFailed as e:
            raise _map_prefetch_error(e)
        return web.json_response(result)

    async def get_instance_prefetch(request: web.Request) -> web.Response:
        instance_id = request.match_info["instance_id"]
        try:
            result = await asyncio.get_running_loop().run_in_executor(
                None, lambda: manager.get_instance_prefetch(instance_id)
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except PrefetchFailed as e:
            raise _map_prefetch_error(e)
        return web.json_response(result)

    async def abort_instance_prefetch(request: web.Request) -> web.Response:
        instance_id = request.match_info["instance_id"]
        try:
            result = await asyncio.get_running_loop().run_in_executor(
                None, lambda: manager.abort_instance_prefetch(instance_id)
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except PrefetchFailed as e:
            raise _map_prefetch_error(e)
        return web.json_response(result)

    def _map_migrate_error(e):
        # the engines' 409 is an explicit precondition refusal (identity
        # mismatch, spent fence, no capacity / drain
        # not converging) with nothing displaced — preserved verbatim so
        # an orchestrator can react to exactly that signal; 404 is a bad
        # destination id; 504 timed out (recovery already ran on the
        # engines); anything else is a gateway/engine failure
        if e.status == 409:
            return web.HTTPConflict(text=str(e))
        if e.status == 404:
            return web.HTTPNotFound(text=str(e))
        if 400 <= e.status < 500:
            return web.HTTPBadRequest(text=str(e))
        if e.status == 504:
            return web.HTTPGatewayTimeout(text=str(e))
        return web.HTTPBadGateway(text=str(e))

    async def migrate_instance(request: web.Request) -> web.Response:
        """Live-migration verb: hand the instance's in-flight and queued
        requests to a sibling serving the same model — transactional,
        fenced, streams keep flowing (docs/operations.md "Draining a
        node without dropping streams"). Body: optional ``dest_id`` to
        pin the destination (default: first eligible sibling)."""
        instance_id = request.match_info["instance_id"]
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:
                raise web.HTTPBadRequest(text="invalid JSON body")
        else:
            body = {}
        dest_id = body.get("dest_id")
        if dest_id is not None and (
            not isinstance(dest_id, str) or not dest_id
        ):
            raise web.HTTPUnprocessableEntity(
                text="dest_id must be a non-empty string"
            )
        try:
            # export + import move KV bytes for seconds; keep the loop free
            result = await _traced_call(
                request,
                lambda: manager.migrate_instance(instance_id, dest_id=dest_id),
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except MigrateFailed as e:
            raise _map_migrate_error(e)
        return web.json_response(result)

    async def drain_instance(request: web.Request) -> web.Response:
        """Node-drain verb: repeat migrate passes until the instance
        reports no live work, leaving it idle and safe to kill while
        every displaced stream keeps flowing through the source's
        proxies."""
        instance_id = request.match_info["instance_id"]
        try:
            result = await _traced_call(
                request, lambda: manager.drain_instance(instance_id)
            )
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except (DrainFailed, MigrateFailed) as e:
            raise _map_migrate_error(e)
        return web.json_response(result)

    async def get_log(request: web.Request) -> web.Response:
        instance_id = request.match_info["instance_id"]
        range_header = request.headers.get("Range")
        if range_header is None:
            start, end, partial = 0, None, False
        else:
            try:
                start, end = parse_range_header(range_header)
            except ValueError as e:
                raise web.HTTPBadRequest(text=str(e))
            partial = True
        try:
            data, total = manager.get_instance_log_bytes(instance_id, start, end)
        except KeyError:
            raise web.HTTPNotFound(text=f"Instance {instance_id} not found")
        except LogRangeNotAvailable as e:
            if not partial:
                # Rangeless GET of a still-empty log is a healthy 200, not 416.
                return web.Response(
                    body=b"",
                    status=HTTPStatus.OK,
                    content_type="application/octet-stream",
                    headers={"Accept-Ranges": "bytes"},
                )
            return web.Response(
                body=b"",
                status=HTTPStatus.REQUESTED_RANGE_NOT_SATISFIABLE,
                content_type="application/octet-stream",
                headers={"Content-Range": f"bytes */{e.total}"},
            )
        actual_end = start + len(data) - 1
        return web.Response(
            body=data,
            status=HTTPStatus.PARTIAL_CONTENT if partial else HTTPStatus.OK,
            content_type="application/octet-stream",
            headers={
                "Accept-Ranges": "bytes",
                "Content-Range": f"bytes {start}-{actual_end}/{total}",
            },
        )

    async def faults_get(request: web.Request) -> web.Response:
        from ..utils import faults

        return web.json_response(faults.describe())

    async def faults_arm(request: web.Request) -> web.Response:
        """Arm launcher-process fault points (launcher.rpc,
        instance.spawn) for tests and fault drills (utils/faults.py)."""
        from ..utils import faults

        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        spec = body.get("spec")
        if not isinstance(spec, str) or not spec:
            # 400 like the engine's mirrored /v1/faults — one convention
            # for drill scripts hitting either surface
            raise web.HTTPBadRequest(text="faults requires a 'spec' string")
        try:
            faults.arm_spec(spec)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        return web.json_response(faults.describe())

    async def faults_reset(request: web.Request) -> web.Response:
        from ..utils import faults

        faults.reset()
        return web.json_response(faults.describe())

    async def exemplars(request: web.Request) -> web.Response:
        """GET /v2/vllm/exemplars: the fleet's SLO-violation exemplars —
        last-N violated requests across every reporting child, each with
        its trace_id, leg-duration breakdown, and owning instance, so an
        operator can jump straight from "attainment is dropping" to one
        child's GET /v1/traces?trace_id= (docs/operations.md)."""
        try:
            fleet = await asyncio.get_running_loop().run_in_executor(
                None, manager.fleet_rollup
            )
        except Exception as e:  # noqa: BLE001 — degraded poll, not a 500
            logger.warning("fleet rollup for exemplars failed", exc_info=True)
            raise web.HTTPServiceUnavailable(text=str(e))
        return web.json_response(
            {
                "slo_exemplars": fleet.get("slo_exemplars") or [],
                "slo_attainment": fleet.get("slo_attainment"),
                "slo_requests_violated": fleet.get(
                    "slo_requests_violated", 0
                ),
            }
        )

    async def traces(request: web.Request) -> web.Response:
        """Export the LAUNCHER process's span ring buffer (create/swap/
        restart verbs + launcher.rpc hops). The engine children export
        their own via GET /v1/traces; together the per-process Chrome
        JSONs merge into one Perfetto timeline (docs/tracing.md)."""
        status, body, ctype = tracing.export_http(
            request.query.get("format", "chrome"),
            trace_id=request.query.get("trace_id") or None,
            clear=request.query.get("clear") in ("1", "true"),
        )
        return web.Response(status=status, text=body, content_type=ctype)

    app.router.add_get("/health", health)
    app.router.add_get("/v2/vllm/traces", traces)
    app.router.add_get("/v2/vllm/exemplars", exemplars)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/", index)
    app.router.add_get("/v2/vllm/faults", faults_get)
    app.router.add_post("/v2/vllm/faults", faults_arm)
    app.router.add_delete("/v2/vllm/faults", faults_reset)
    app.router.add_get("/v2/vllm/instances/watch", watch)
    app.router.add_post("/v2/vllm/instances", create_instance)
    app.router.add_put("/v2/vllm/instances/{instance_id}", create_named_instance)
    app.router.add_delete("/v2/vllm/instances/{instance_id}", delete_instance)
    app.router.add_delete("/v2/vllm/instances", delete_all)
    app.router.add_get("/v2/vllm/instances", get_all)
    app.router.add_get("/v2/vllm/instances/{instance_id}", get_one)
    app.router.add_get("/v2/vllm/instances/{instance_id}/log", get_log)
    app.router.add_post("/v2/vllm/instances/{instance_id}/swap", swap_instance)
    app.router.add_post(
        "/v2/vllm/instances/{instance_id}/prefetch", prefetch_instance
    )
    app.router.add_get(
        "/v2/vllm/instances/{instance_id}/prefetch", get_instance_prefetch
    )
    app.router.add_delete(
        "/v2/vllm/instances/{instance_id}/prefetch", abort_instance_prefetch
    )
    app.router.add_post(
        "/v2/vllm/instances/{instance_id}/migrate", migrate_instance
    )
    app.router.add_post(
        "/v2/vllm/instances/{instance_id}/drain", drain_instance
    )

    async def on_shutdown(app: web.Application) -> None:
        manager.stop_all_instances()

    app.on_shutdown.append(on_shutdown)
    return app


def _watch_sentinel(manager: EngineProcessManager, instance_id: str) -> None:
    """Arm crash detection for a just-created instance (needs a running
    event loop, hence done in the handler, not the manager)."""
    instance = manager.instances.get(instance_id)
    if instance is not None:
        try:
            instance.start_sentinel_watcher(manager._on_instance_stopped)
        except RuntimeError:
            logger.warning("no running loop; sentinel watcher not armed")
