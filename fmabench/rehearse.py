"""AOT-compile each cell's serving programs for a described v5e chip.

A script run by hand before the first chip call, not a test (the repo's one
topology-describing test file is ``tests/test_chip_compile.py``; a second
would skip in silence under the driver's workers). On-chip-measurement
guide section 2.3: the TPU compiler is installed here and compiles for a
chip that is described and not attached, so what Mosaic or XLA would refuse
on the chip — a tiling, too much fast memory, a program that does not fit —
is refused here, at no chip time. Nothing runs: no result, no time.

    JAX_PLATFORMS=cpu python -m fmabench.rehearse [--workload <name>]

For every cell it compiles the decode chunk and the largest prefill and
suffix buckets of the cell's engine options at the configuration's real
sizes, on a one-device mesh of the described ``v5e:2x2`` topology (the
engine's own single-device path asks ``jax.devices()``, which is the CPU
here, so the mesh path stands in: same programs, kernels under a one-way
shard_map), and prints ``memory_analysis()`` bytes beside the reckoned
weights + KV pool.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    p = argparse.ArgumentParser(prog="fmabench.rehearse")
    p.add_argument("--workload", action="append")
    args = p.parse_args()
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        print("run with JAX_PLATFORMS=cpu: this describes a chip, it never "
              "touches one", file=sys.stderr)
        return 2

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from llm_d_fast_model_actuation_tpu.engine import exec_pool, server
    from llm_d_fast_model_actuation_tpu.ops import attention
    from llm_d_fast_model_actuation_tpu.parallel.mesh import AXES

    from . import spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1, 1, 1), AXES)
    attention.set_pallas_interpret(False)
    bench = spec.benchmark()
    rc = 0
    for w in bench["workloads"]:
        if args.workload and w["name"] not in args.workload:
            continue
        cell = spec.Cell(bench, w["name"])
        dims = cell.dims
        model = cell.family.part("program").build(dims)
        eargs = server.make_arg_parser().parse_args(
            ["--model", "tiny", *cell.engine_options(False)]
        )
        import dataclasses

        model = dataclasses.replace(model, attention_impl="pallas")
        cfg = server.EngineConfig(
            model=model, max_batch=eargs.max_batch, page_size=eargs.page_size,
            num_pages=eargs.num_pages, attention_impl="pallas",
            decode_chunk=eargs.decode_chunk or 32,
            max_prefill_tokens=eargs.max_prefill_tokens,
        )
        keys = cell.family.keys
        reckoned = keys.param_count(dims) * 2 + keys.kv_bytes(
            dims, eargs.num_pages, eargs.page_size
        )
        lens = cell.traffic["warmup"]["prompt_lens"]
        limit = eargs.max_prefill_tokens or max(lens)
        programs = [("chunk", cfg.decode_chunk),
                    ("prefill", min(limit, max(lens)))]
        if max(lens) > limit:
            programs.append(("suffix", limit))
        for program, bucket in programs:
            t0 = time.monotonic()
            row = {"cell": cell.name, "program": program, "bucket": bucket,
                   "reckoned_state_bytes": reckoned}
            try:
                compiled = exec_pool.compile_program(
                    cfg, program, bucket, mesh=mesh
                )
                ma = compiled.memory_analysis()
                row.update(
                    compiled=True,
                    kernel="tpu_custom_call" in compiled.as_text(),
                    argument_bytes=ma.argument_size_in_bytes,
                    output_bytes=ma.output_size_in_bytes,
                    temp_bytes=ma.temp_size_in_bytes,
                    alias_bytes=ma.alias_size_in_bytes,
                    seconds=round(time.monotonic() - t0, 1),
                )
            except Exception as e:  # noqa: BLE001 — the refusal is the finding
                row.update(compiled=False, error=f"{type(e).__name__}: {e}"[:600])
                rc = 1
            print(json.dumps(row), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
