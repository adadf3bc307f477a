"""The child that holds the chip: the program's own engine server, with the
cell's configuration registered under its name.

    python -m fmabench.serve --config-file <path> -- <engine.server options>

It adds the configuration file's sizes to the server's ``MODEL_CONFIGS``
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


def build_model_config(config: dict):
    """The program's config object for an HF-keyed configuration file."""
    from llm_d_fast_model_actuation_tpu.models import llama, moe

    from . import spec

    d = spec.model_dims(config)
    common = dict(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], head_dim=d["head_dim"],
        intermediate_size=d["intermediate_size"], rope_theta=d["rope_theta"],
        rms_eps=d["rms_eps"], max_seq_len=d["max_context"],
    )
    if d["num_experts"] > 1:
        return moe.MoeConfig(
            num_experts=d["num_experts"],
            experts_per_token=d["experts_per_token"], **common,
        )
    return llama.LlamaConfig(**common)


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
    server.MODEL_CONFIGS[args.model_name] = lambda: build_model_config(config)
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
