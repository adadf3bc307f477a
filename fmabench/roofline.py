"""What a kernel's algorithm must move and compute per call, from shapes.

Each function takes the run's ``shapes`` (the configuration's sizes, the
engine options, and what the client saw of the live contexts) and returns
``{"bytes": ..., "flops": ...}`` for ONE call of the kernel, or ``None``
when the run gives it nothing to reckon from. Kept with the benchmark so
that no PR that claims a gain changes the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2


def paged_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Paged decode attention for one layer of one decode step: every live
    sequence reads its whole context's K and V once (GQA: each KV head's
    page is read once for its group of query heads), plus q in and o out.

    ``live_kv_tokens`` is the mean, over the profiled stretch, of the sum
    of the context lengths of the sequences that were decoding, and
    ``live_seqs`` their mean number — both from the client's records.
    """
    tokens = shapes.get("live_kv_tokens")
    seqs = shapes.get("live_seqs")
    if not tokens or not seqs:
        return None
    kvh, hd, heads = shapes["num_kv_heads"], shapes["head_dim"], shapes["num_heads"]
    kv_bytes = tokens * kvh * hd * 2 * BF16  # K and V
    qo_bytes = seqs * heads * hd * 2 * BF16  # q read, o written
    flops = 2.0 * 2.0 * tokens * heads * hd  # q.K and p.V
    return {"bytes": kv_bytes + qo_bytes, "flops": flops}
