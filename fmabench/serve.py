"""The child that holds the chip: the program's own engine server, with the
cell's configuration registered under its name.

    python -m fmabench.serve --config-file <path> -- <engine.server options>

It adds the configuration file, as its family's ``program.py`` builds it
(``fmabench/families/<family>/``), to the server's ``MODEL_CONFIGS``
and calls the server's own ``main``: the same entry, scheduler, cache and
kernels as any other model (``python -m
llm_d_fast_model_actuation_tpu.engine.server``). Two things here are the
benchmark's own, because only the process that holds the chip can read
them: SIGUSR1 writes the devices' ``memory_stats()`` to ``--memory-file``
(the program's ``/v1/stats`` gives bytes in use, not the peak), and
``--require-platform`` makes the child fail instead of serving from a
platform the cell did not ask for.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
from typing import List


def build_model_config(config: dict, data_dir: str = ""):
    """The program's config object for a configuration file: its family's
    ``program.py`` builds it from its family's sizes."""
    from . import spec

    family = spec.family_of(config, data_dir)
    return family.part("program").build(family.dims(config))


def _dump_memory(path: str) -> None:
    import jax

    rows = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        rows.append({
            "id": dev.id,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(rows, f)
    os.replace(tmp, path)


def main(argv: List[str]) -> None:
    p = argparse.ArgumentParser(prog="fmabench.serve")
    p.add_argument("--config-file", required=True)
    p.add_argument("--model-name", required=True)
    p.add_argument("--data-dir", default="",
                   help="a rehearsal's own data files, looked in first")
    p.add_argument("--memory-file", default="")
    p.add_argument("--require-platform", default="")
    p.add_argument("--pallas-interpret", action="store_true",
                   help="CPU rehearsal only: Pallas kernels interpreted")
    args, engine_argv = p.parse_known_args(argv)
    if engine_argv and engine_argv[0] == "--":
        engine_argv = engine_argv[1:]

    from llm_d_fast_model_actuation_tpu.engine import server

    from . import spec

    config = spec.config_file(args.config_file)
    server.MODEL_CONFIGS[args.model_name] = lambda: build_model_config(
        config, args.data_dir
    )
    if args.pallas_interpret:
        from llm_d_fast_model_actuation_tpu.ops import attention

        attention.set_pallas_interpret(True)
    if args.require_platform:
        import jax

        platform = jax.devices()[0].platform
        if platform != args.require_platform:
            raise SystemExit(
                f"fmabench.serve: this cell needs a {args.require_platform} "
                f"device and JAX has {platform}: no fallback"
            )
    if args.memory_file:
        signal.signal(
            signal.SIGUSR1, lambda *_: _dump_memory(args.memory_file)
        )
    server.main(["--model", args.model_name, *engine_argv])


if __name__ == "__main__":
    main(sys.argv[1:])
