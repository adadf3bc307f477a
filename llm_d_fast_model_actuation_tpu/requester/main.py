"""Requester stub main: SPI + probes servers in one process.

Reference parity: cmd/requester/main.go:32-85 (real chips) and
cmd/test-requester (emulated allocation for hardware-less e2e). Backends:

  * ``--backend real``   — chips from the native tpuinfo shim (or /dev/accel
    fallback), HBM usage from the shim;
  * ``--backend env``    — chips from $TPU_VISIBLE_DEVICES + a chip-map file
    (what the kube scheduler/device plugin would have granted);
  * ``--backend static`` — explicit ``--chips a,b,c`` (tests);
  * ``--backend alloc``  — claim ``--alloc-count`` chips of ``--chips`` on
    ``--node`` from the shared ``chip-allocations`` ConfigMap with the
    optimistic-concurrency loop (reference test-requester contention
    emulation, cmd/test-requester/gpu-allocation.go:41-257); claims are
    released on shutdown.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
from typing import Dict, List

from aiohttp import web

from .probes import ProbesServer
from .spi import LogSink, ReadyFlag, SpiServer

logger = logging.getLogger(__name__)


def resolve_chips(args: argparse.Namespace, should_stop=None):
    """Returns (chip_ids, cleanup_fn_or_None)."""
    if args.backend == "static":
        return [c for c in args.chips.split(",") if c], None
    if args.backend == "alloc":
        from ..controller.kubestore import KubeStore
        from .allocation import ChipAllocator

        pool = [c for c in args.chips.split(",") if c]
        if not (args.api_base and args.node and pool and args.alloc_count > 0):
            raise RuntimeError(
                "alloc backend needs --api-base, --node, --chips (the node "
                "pool) and --alloc-count"
            )
        store = KubeStore(args.api_base, args.namespace, kinds=None)
        holder = args.pod_name or os.environ.get("POD_NAME") or f"req-{os.getpid()}"
        alloc = ChipAllocator(store, args.namespace, args.node, holder)
        try:
            chips = alloc.allocate(
                args.alloc_count,
                pool,
                timeout_s=args.alloc_timeout,
                should_stop=should_stop,
            )
        except Exception:
            alloc.release()  # never leak a partial/prior claim on failure
            raise
        return chips, alloc.release
    if args.backend == "env":
        from ..parallel.topology import ChipMap
        import json

        node = os.environ.get("NODE_NAME", "")
        path = args.chip_map_path or os.environ.get("CHIP_MAP_PATH", "")
        visible = os.environ.get("TPU_VISIBLE_DEVICES", "")
        if not (node and path and visible):
            raise RuntimeError(
                "env backend needs NODE_NAME, CHIP_MAP_PATH and TPU_VISIBLE_DEVICES"
            )
        with open(path) as f:
            cm = ChipMap.parse(json.load(f))
        host = cm.host(node)
        if host is None:
            raise RuntimeError(f"node {node} not in chip map")
        want = {int(i) for i in visible.split(",")}
        return [c.chip_id for c in host.chips if c.index in want], None
    # real
    from ..launcher.chiptranslator import enumerate_real

    return [c.chip_id for c in enumerate_real()[0].chips], None


def memory_backend(args: argparse.Namespace, chip_ids: List[str]):
    if args.backend == "real":
        def usage() -> Dict[str, int]:
            from ..native import tpuinfo

            all_usage = tpuinfo.hbm_usage()
            return {c: all_usage.get(c, 0) for c in chip_ids}

        return usage
    return lambda: {c: 0 for c in chip_ids}


async def serve(args: argparse.Namespace) -> None:
    # SIGTERM must run the cleanup path — the alloc backend's ConfigMap
    # claims are released on exit (gpu-allocation.go's defer-release
    # equivalent) — so install handlers BEFORE the allocation runs (which is
    # pushed to a thread below so the loop stays responsive to the signal).
    import signal

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass

    ready = ReadyFlag(False)
    sink = LogSink()
    # the alloc backend blocks (CAS polling up to --alloc-timeout): run it in
    # a thread so the installed SIGTERM handler can actually fire mid-wait
    alloc_task = asyncio.create_task(
        asyncio.to_thread(resolve_chips, args, stop.is_set)
    )
    stop_task = asyncio.create_task(stop.wait())
    done, _ = await asyncio.wait(
        {alloc_task, stop_task}, return_when=asyncio.FIRST_COMPLETED
    )
    if stop_task in done and alloc_task not in done:
        # terminated while waiting for chips: the allocator sees stop on its
        # next poll, releases anything it won, and raises
        try:
            await alloc_task
        except Exception:
            pass
        return
    stop_task.cancel()
    chips, cleanup = await alloc_task
    logger.info("requester stub: chips=%s", chips)
    runners = []
    try:
        spi = SpiServer(chips, ready, memory_backend(args, chips), sink)
        probes = ProbesServer(ready)
        for app, port in (
            (spi.build_app(), args.spi_port),
            (probes.build_app(), args.probes_port),
        ):
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, args.host, port)
            await site.start()
            runners.append(runner)
        logger.info("SPI on :%s, probes on :%s", args.spi_port, args.probes_port)
        await stop.wait()
    finally:
        # covers server-startup failures too: a claim must never outlive
        # the process that holds it
        for runner in runners:
            await runner.cleanup()
        if cleanup is not None:
            cleanup()  # release ConfigMap chip claims (alloc backend)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="fma-tpu-requester")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument(
        "--spi-port", type=int, default=int(os.environ.get("SPI_PORT", "8081"))
    )
    p.add_argument(
        "--probes-port",
        type=int,
        default=int(os.environ.get("PROBES_PORT", "8080")),
    )
    p.add_argument(
        "--backend", choices=("real", "env", "static", "alloc"), default="real"
    )
    p.add_argument(
        "--chips",
        default="",
        help="comma-separated chip IDs (static: owned outright; "
        "alloc: the node's contended pool)",
    )
    p.add_argument("--chip-map-path", default="")
    # alloc backend (ConfigMap-based contention emulation)
    p.add_argument("--api-base", default="", help="apiserver base URL")
    p.add_argument("--namespace", default="default")
    p.add_argument("--node", default=os.environ.get("NODE_NAME", ""))
    p.add_argument("--pod-name", default=os.environ.get("POD_NAME", ""))
    p.add_argument("--alloc-count", type=int, default=1)
    p.add_argument("--alloc-timeout", type=float, default=60.0)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    asyncio.run(serve(args))


if __name__ == "__main__":
    main()
