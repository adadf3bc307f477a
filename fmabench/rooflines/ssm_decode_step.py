"""One Mamba-2 layer's state update in a decode step (the state-space
recurrence, one step a live sequence): the layer's recurrent state h, heads x
head_dim x state channels in float32, and its convolution tail, kernel - 1
inputs of conv_dim channels in bfloat16, are read once and written once for
every live sequence: 2 x 4,255,744 B at the published sizes. Whatever
implements the step must move these bytes; an implementation that passes over
h more often, or that reads and writes the slots of sequences that are not
live, moves more and reads a lower share. The arithmetic (the decay, the
rank-one update and the contraction with C: 3 multiply-adds an element of h)
is far below the ridge, so the bytes decide; the flops are given for
completeness.

The reader divides the matched ops' summed time by their number, and the
program's step is ``CALLS_A_LAYER`` ops a layer today (XLA: a pass that reads
h for h C, then the update in place), both matched by
``metrics/ssm_decode_hbm_pct.ssmchat.json``. So what is returned is the
layer's need spread over those calls, and the share is the layer's need over
the calls' summed time. A step fused into one op renames the ops, the metric
falls silent, and the PR that adds its reader sets the count beside it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

F32, BF16 = 4, 2
#: device ops one Mamba-2 layer's state update is, in the matched program
CALLS_A_LAYER = 2


def ssm_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    seqs = shapes.get("live_seqs")
    if not seqs or not shapes.get("mamba_heads"):
        return None
    elems = (
        shapes["mamba_heads"] * shapes["mamba_head_dim"] * shapes["ssm_state_size"]
    )
    tail = (shapes["conv_kernel"] - 1) * shapes["conv_dim"]
    per_seq = elems * F32 + tail * BF16
    return {
        # read once, written once, a layer; a call's share of it
        "bytes": seqs * 2 * per_seq / CALLS_A_LAYER,
        # h C, the decay and the rank-one update: 3 multiply-adds
        "flops": seqs * 6.0 * elems / CALLS_A_LAYER,
    }
