"""The routed experts of one expert layer in a decode step, where the experts
work in a LATENT space and this chip holds a SHARE of its router's experts:
two matrices an expert (up, then down after the squared ReLU).

A step of n live sequences makes n x k assignments over the router's whole
width W, of which this chip holds H experts. Under uniform routing, which
seeded random weights give, a held expert gets none of them with probability
(1 - k/W)^n, so H x (1 - (1 - k/W)^n) held experts are touched and the two
matrices of each, ``moe_latent_size`` x ``expert_size`` in bfloat16, must be
read; the n x k x H/W rows routed to held experts go in and come out once a
matrix, one side ``moe_latent_size`` wide and the other ``expert_size``. The
experts read the layer's input projected into the latent space, not the
residual stream: ``moe_share_decode_step`` would take ``hidden_size`` for
their input and count four times the bytes. The rows routed to absent experts
are another chip's work and are not counted. Decode is far below the MXU's
ridge, so the bytes decide; the flops are given for completeness.

The reader divides the matched ops' summed time by their number. The program's
decode step computes every held expert on every row (a decode batch of 128
touches all 128 either way) as ``CALLS_A_LAYER`` op a layer today: XLA fuses
the up matmul, the activation, the down matmul and the weighted sum into ONE
fusion, which ``metrics/latent_moe_decode_hbm_pct.ssmchat.json`` matches. So
what is returned is the layer's need, both matrices, spread over those calls.
A program that makes one call a matrix (the grouped matmuls the prompt
segments use) sets the count to 2 beside its reader.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2
#: matrices a routed expert has
MATRICES = 2
#: device ops one layer's routed experts are, in the matched program
CALLS_A_LAYER = 1


def latent_moe_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    seqs = shapes.get("live_seqs")
    if not seqs or not shapes.get("moe_latent_size") or not shapes.get("experts_held"):
        return None
    held, width, k = (
        shapes["experts_held"], shapes["router_width"], shapes["experts_per_token"],
    )
    lat, f = shapes["moe_latent_size"], shapes["expert_size"]
    touched = held * (1.0 - (1.0 - k / width) ** seqs)
    rows = seqs * k * held / width
    per_matrix = touched * lat * f * BF16 + rows * (lat + f) * BF16
    return {
        "bytes": MATRICES * per_matrix / CALLS_A_LAYER,
        "flops": MATRICES * 2.0 * rows * lat * f / CALLS_A_LAYER,
    }
