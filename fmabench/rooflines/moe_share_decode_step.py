"""One grouped expert matmul of a routed layer that holds a SHARE of its
router's experts, in a decode step (the layer makes three a step: gate, up,
down; each is one call).

A step of n live sequences makes n x k assignments over the router's whole
width W, of which this chip holds H experts. Under uniform routing, which
seeded random weights give, a held expert gets none of them with probability
(1 - k/W)^n, so H x (1 - (1 - k/W)^n) held experts are touched and one
matrix of each, hidden x expert width in bfloat16, must be read; the
n x k x H/W rows routed to held experts go in and come out once. The rows
routed to absent experts are another chip's work and are not counted: the
accepted ``moe_decode_step`` would count all W experts as held here and read
over 100%. Decode is far below the MXU's ridge, so the bytes decide; the
flops are given for completeness.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2


def moe_share_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    seqs = shapes.get("live_seqs")
    if not seqs or not shapes.get("experts_held") or not shapes.get("router_width"):
        return None
    held, width, k = (
        shapes["experts_held"], shapes["router_width"], shapes["experts_per_token"],
    )
    h, f = shapes["hidden_size"], shapes["expert_size"]
    touched = held * (1.0 - (1.0 - k / width) ** seqs)
    rows = seqs * k * held / width
    return {
        "bytes": touched * h * f * BF16 + rows * (h + f) * BF16,
        "flops": 2.0 * rows * h * f,
    }
