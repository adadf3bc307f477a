"""Actuation benchmark harness (reference: inference_server/benchmark/).

Measures the measurement model of `benchmark.md:24-133`: T_actuation,
T_wake, Hot/Warm hit rates, T_cold_launcher, T_instance_create across the
baseline / scaling / new-variant scenarios, in `simulated` mode (in-memory
control plane + latency-injected fakes) or against a live stack.
"""

from .harness import ActuationBenchmark, BenchmarkConfig
from .scenarios import run_baseline, run_new_variant, run_scaling

__all__ = [
    "ActuationBenchmark",
    "BenchmarkConfig",
    "run_baseline",
    "run_scaling",
    "run_new_variant",
]
