"""One grouped expert matmul of the routed layer in a decode step (the
layer makes three a step: gate, up, down; each is one call).

A step of n live sequences sends n x k rows to the experts. Under uniform
routing, which seeded random weights give, an expert gets none of them with
probability (1 - k/E)^n, so E x (1 - (1 - k/E)^n) experts are touched and
one matrix of each, hidden x expert width in bfloat16, must be read; the
rows themselves go in and come out once. Decode is far below the MXU's
ridge, so the bytes decide; the flops are given for completeness.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2


def moe_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    seqs = shapes.get("live_seqs")
    if not seqs or not shapes.get("num_experts"):
        return None
    experts, k = shapes["num_experts"], shapes["experts_per_token"]
    h, f = shapes["hidden_size"], shapes["expert_size"]
    touched = experts * (1.0 - (1.0 - k / experts) ** seqs)
    rows = seqs * k
    return {
        "bytes": touched * h * f * BF16 + rows * (h + f) * BF16,
        "flops": 2.0 * rows * h * f,
    }
