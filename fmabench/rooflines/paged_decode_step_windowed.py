"""Paged decode attention for one call of the kernel in a model whose
layers are of two kinds (``window_layout``): the mean over the layers.

A full-attention layer reads every live sequence's whole context, as
``roofline.paged_decode_step`` counts. A window layer reads, for a context
c, min(c, window) positions. The harness hands a roofline function only the
SUM of the live contexts and their NUMBER, from which sum(min(c, window))
cannot be formed; so the LEAST that those two numbers allow is counted (the
sum concentrated in as few sequences as ``max_context`` permits, each of
which then reads one window), and the share this gives is never overstated.
The exact count needs the per-sequence contexts handed to the functions
(PERF.md section 7).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2


def paged_decode_step_windowed(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    tokens = shapes.get("live_kv_tokens")
    seqs = shapes.get("live_seqs")
    layout = shapes.get("window_layout")
    if not tokens or not seqs or not layout:
        return None
    window, longest = shapes["window"], shapes["max_context"]
    full = min(seqs, tokens // longest)  # sequences at the longest context
    rest = tokens - full * longest
    least_windowed = full * window + min(rest, window)
    n_window = sum(layout)
    per_call = (
        (len(layout) - n_window) * tokens + n_window * least_windowed
    ) / len(layout)
    kvh, hd, heads = shapes["num_kv_heads"], shapes["head_dim"], shapes["num_heads"]
    kv_bytes = per_call * kvh * hd * 2 * BF16  # K and V
    qo_bytes = seqs * heads * hd * 2 * BF16  # q read, o written
    return {"bytes": kv_bytes + qo_bytes, "flops": 2.0 * 2.0 * per_call * heads * hd}
