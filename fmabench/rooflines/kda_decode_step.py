"""One KDA layer's state update in a decode step (the delta rule with a
decay a channel, one recurrence step a live sequence): the layer's recurrent
state S, heads x d x d in float32, and its convolution tail, kernel - 1
inputs of conv_dim channels in bfloat16, are read once and written once for
every live sequence: 2 x 2,170,880 B at the published sizes. Whatever
implements the step must move these bytes; an implementation that passes
over S more often, or that reads and writes the slots of sequences that are
not live, moves more and reads a lower share. The arithmetic (a few
multiply-adds an element of S) is far below the ridge, so the bytes decide;
the flops are given for completeness.

The reader divides the matched ops' summed time by their number, and the
program's step is ``CALLS_A_LAYER`` ops a layer today (XLA: a pass that
reads S for S^T(alpha k) and S^T(alpha q), then the update in place), both
matched by ``metrics/kda_decode_hbm_pct.decodemix.json``. So what is
returned is the layer's need spread over those calls, and the share is the
layer's need over the calls' summed time. A step fused into one op renames
the ops, the metric falls silent, and the PR that adds its reader sets the
count beside it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

F32, BF16 = 4, 2
#: device ops one KDA layer's state update is, in the matched program
CALLS_A_LAYER = 2


def kda_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    seqs = shapes.get("live_seqs")
    if not seqs or not shapes.get("kda_heads"):
        return None
    elems = shapes["kda_heads"] * shapes["kda_head_dim"] ** 2
    tail = (shapes["conv_kernel"] - 1) * shapes["conv_dim"]
    per_seq = elems * F32 + tail * BF16
    return {
        # read once, written once, a layer; a call's share of it
        "bytes": seqs * 2 * per_seq / CALLS_A_LAYER,
        # S^T k, S^T q, the decay and the rank-one update: 4 multiply-adds
        "flops": seqs * 8.0 * elems / CALLS_A_LAYER,
    }
