"""fmabench: the benchmark of fma-tpu (see fmabench/README.md).

One command runs one cell once:

    python -m fmabench --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
