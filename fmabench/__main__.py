"""``python -m fmabench``: one run of one cell; the last line is the result."""

import time

T_START = time.monotonic()  # set-up counts from here

import argparse  # noqa: E402
import sys  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(prog="fmabench")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--benchmark", default=None,
                   help="another BENCHMARK.json (tests, rehearsals)")
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal of the control flow: needs "
                   "JAX_PLATFORMS=cpu, prints no device metric")
    p.add_argument("--serve-module", default="fmabench.serve",
                   help="the module run as the engine child (a test puts a "
                   "broken one here to see `correct` come out false)")
    p.add_argument("--control", default="", choices=["", "int8"],
                   help="also read the low-precision control on the same "
                   "requests (by hand, when limits are set)")
    args = p.parse_args()
    from . import harness, spec

    if args.benchmark is None:
        args.benchmark = spec.BENCHMARK_JSON
    return harness.run_cell(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
