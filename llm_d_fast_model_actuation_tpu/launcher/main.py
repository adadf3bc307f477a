"""Launcher CLI: preload, then serve the instance-management REST API.

Reference parity (launcher.py:900-967): ``--mock-gpus`` family becomes
``--mock-chips``; the launcher imports JAX + the engine modules *before* any
fork so children inherit warm modules, and it exports a persistent XLA
compilation-cache directory shared by every instance (on TPU, compilation —
not weight loading — dominates cold start; a shared cache turns repeat model
launches into cache hits). It never initializes a JAX backend itself: on TPU
a process that has one holds the chip, and its children could not.
"""

from __future__ import annotations

import argparse
import logging
import os

from aiohttp import web

logger = logging.getLogger(__name__)


def preload() -> None:
    """Import the heavy modules once, pre-fork, and arm the persistent
    compilation cache (the TPU analogue of the reference's 'launcher imported
    vLLM before forking', launcher.py:836-885). Every child of this launcher
    shares the directory (utils/compile_cache.py), and the engine's
    serialized-executable spill rides next to it (engine/exec_pool.py), so a
    pooled executable survives instance restarts and even seeds sibling
    instances of the same model."""
    import jax

    from ..engine import server as _server  # noqa: F401  (engine modules warm)
    from ..models import llama as _llama  # noqa: F401
    from ..utils import compile_cache

    logger.info(
        "preloaded jax %s; compile cache at %s",
        jax.__version__, compile_cache.arm() or "(none: held to the CPU)",
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="fma-tpu-launcher")
    p.add_argument("--host", default="0.0.0.0")
    # FMA_LAUNCHER_PORT: the dual-pods controller injects this when a
    # hostNetwork node already has a launcher on the default port (same-node
    # port collision; the per-pod launcher-port annotation carries the same
    # value for the controller's transport)
    p.add_argument(
        "--port",
        type=int,
        default=int(os.environ.get("FMA_LAUNCHER_PORT", "8001")),
    )
    p.add_argument("--log-level", default="info")
    p.add_argument("--mock-chips", action="store_true")
    p.add_argument("--mock-chip-count", type=int, default=8)
    p.add_argument("--mock-topology", default="")
    p.add_argument("--chip-map-path", default="")
    p.add_argument("--log-dir", default="")
    p.add_argument(
        "--compile-cache-dir",
        default="",
        help="default for JAX_COMPILATION_CACHE_DIR when the environment "
        "does not set it (utils/compile_cache.py has the rule)",
    )
    p.add_argument("--no-preload", action="store_true")
    # Crash supervision (launcher/manager.py RestartPolicy): 0 keeps the
    # pre-existing report-only behavior (controller re-pair heals crashes).
    p.add_argument(
        "--restart-budget",
        type=int,
        default=int(os.environ.get("FMA_RESTART_BUDGET", "0")),
        help="supervised restarts per crash loop for a crashed engine "
        "child (0 = report-only); a child that stays up past the reset "
        "window earns its budget back",
    )
    p.add_argument(
        "--restart-backoff",
        type=float,
        default=0.5,
        help="first restart delay (s); doubles per attempt with jitter",
    )
    p.add_argument(
        "--restart-backoff-max",
        type=float,
        default=30.0,
        help="backoff ceiling (s) for supervised restarts",
    )
    p.add_argument(
        "--restart-reset-window",
        type=float,
        default=300.0,
        help="uptime (s) after which a restarted child's crash counter "
        "resets (budget bounds crash loops, not lifetime restarts)",
    )
    p.add_argument(
        "--notify-pod",
        action="store_true",
        help="run the state-change reflector in-process (instead of the "
        "notifier sidecar): patch the launcher Pod's instance-signature "
        "annotation on every instance state change (needs POD_NAME/NAMESPACE)",
    )
    args = p.parse_args(argv)

    logging.basicConfig(level=getattr(logging, args.log_level.upper(), logging.INFO))
    if args.compile_cache_dir:
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", args.compile_cache_dir
        )
    if not args.no_preload:
        preload()

    from ..utils import faults
    from .chiptranslator import ChipTranslator
    from .manager import EngineProcessManager, RestartPolicy
    from .rest import build_app

    # FMA_FAULTS armed pre-fork: launcher-process points (launcher.rpc,
    # instance.spawn) fire here; engine children re-load their own env
    faults.load_env()

    translator = ChipTranslator.create(
        mock_chips=args.mock_chips,
        mock_chip_count=args.mock_chip_count,
        mock_topology=args.mock_topology,
        chip_map_path=args.chip_map_path or None,
    )
    restart_policy = None
    if args.restart_budget > 0:
        restart_policy = RestartPolicy(
            budget=args.restart_budget,
            backoff_s=args.restart_backoff,
            backoff_max_s=args.restart_backoff_max,
            reset_window_s=args.restart_reset_window,
        )
    manager = EngineProcessManager(
        translator, log_dir=args.log_dir, restart_policy=restart_policy
    )
    app = build_app(manager)

    if args.notify_pod:
        import asyncio

        from .notifier import InstanceStateNotifier, kubectl_patcher

        pod_name = os.environ.get("POD_NAME", "")
        namespace = os.environ.get("NAMESPACE", "")
        if not pod_name or not namespace:
            p.error("--notify-pod needs POD_NAME and NAMESPACE env (Downward API)")

        async def lister():
            return manager.get_all_instances_status().get("instances", [])

        async def watcher(since_revision: int):
            # cursor = since_revision (tracked by the notifier), so events
            # published between connect and first read are replayed
            return manager.broadcaster.subscribe(since_revision=since_revision)

        notifier = InstanceStateNotifier(
            lister, kubectl_patcher(pod_name, namespace), watcher=watcher
        )

        async def start_notifier(app):
            app["notifier_task"] = asyncio.get_running_loop().create_task(
                notifier.run()
            )

        async def stop_notifier(app):
            notifier.stop()
            task = app["notifier_task"]
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

        app.on_startup.append(start_notifier)
        app.on_cleanup.append(stop_notifier)
    logger.info(
        "launcher serving on %s:%s (%s chips, mode %s)",
        args.host,
        args.port,
        len(translator.chip_ids()),
        translator.mode,
    )
    web.run_app(app, host=args.host, port=args.port, print=None)


if __name__ == "__main__":
    main()
