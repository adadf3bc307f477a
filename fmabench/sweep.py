"""Find an open mix's knee, once, by a sweep on the chip (by hand).

    python -m fmabench.sweep --workload <open-loop cell> --rates 4,8,12,16 \
        --seconds 20 --seed 1

One engine child serves every stage. A stage offers the mix at a fixed rate
for ``--seconds`` (after the mix's own warm-up stretch at that rate) and
prints what came back: requests due, completed, left unfinished at the
close, and the tails. The knee is the highest rate at which completions
keep up with arrivals — the unfinished count stays near the number in
service and the time to first token does not grow through the stage. The
cell's traffic file then fixes ``rate_rps`` at about four fifths of it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

from . import client, harness, spec, traffic


def main() -> int:
    p = argparse.ArgumentParser(prog="fmabench.sweep")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests/s")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args()
    cell = spec.Cell(spec.benchmark(), args.workload)
    if cell.traffic["kind"] != "open":
        print("only an open mix has a knee", file=sys.stderr)
        return 2
    out_dir = os.path.join(spec.OUT_DIR, cell.name + ".sweep")
    os.makedirs(out_dir, exist_ok=True)
    config_path = os.path.join(out_dir, "config_as_run.json")
    with open(config_path, "w", encoding="utf-8") as f:
        json.dump(cell.config, f)
    port = client.free_port()
    base = f"http://127.0.0.1:{port}"
    argv = client.server_argv(
        cell, config_path, port, args.seed, False,
        os.path.join(out_dir, "memory.json"), "tpu",
    )
    vocab = cell.dims["vocab_size"]
    with client.Child("server", argv, out_dir) as child:
        client.wait_healthy(base + "/health", child, 1100)
        asyncio.run(harness.warmup_ladder(base, cell.traffic, vocab, args.seed))
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = {**cell.traffic, "rate_rps": rate}
            hooks = harness.Hooks(base, False, "", args.seconds)
            win = asyncio.run(
                harness.drive_open(base, mix, vocab, args.seed + i, hooks)
            )
            ttft = win.series.get("ttft_ms") or [0.0]
            # does the wait grow through the stage? first against last third
            recs = sorted((r for r in win.records if r.ok), key=lambda r: r.due)
            third = max(1, len(recs) // 3)
            early = [1e3 * (r.first - r.due) for r in recs[:third]] or [0.0]
            late = [1e3 * (r.first - r.due) for r in recs[-third:]] or [0.0]
            harness.log(
                "sweep_stage", rate_rps=rate, **win.notes,
                out_tokens_per_s=round(win.e2e["out_tokens_per_s"], 1),
                tpot_p95_ms=round(win.e2e.get("tpot_p95_ms", 0.0), 2),
                ttft_p50_ms=round(traffic.percentile(ttft, 50), 1),
                ttft_p95_ms=round(traffic.percentile(ttft, 95), 1),
                ttft_p50_first_third_ms=round(traffic.percentile(early, 50), 1),
                ttft_p50_last_third_ms=round(traffic.percentile(late, 50), 1),
            )
            # the next stage starts from an empty engine: the cancelled
            # requests are aborted server-side within a few loop turns
            t0 = time.monotonic()
            while time.monotonic() - t0 < 60:
                if client.http("GET", base + "/v1/stats")["queue_depth"] == 0:
                    break
                time.sleep(0.5)
            time.sleep(3.0)
            harness.log("sweep_drained", seconds=round(time.monotonic() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
