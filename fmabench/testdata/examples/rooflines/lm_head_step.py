"""Example of a roofline function added as a file (``fmabench/README.md``,
"A per-layer metric"): the output head of one decode step. ``shapes``
holds every size the cell's family gives (``dims``) and what the client
saw of the live contexts, so a function written for a new family reads
that family's own keys."""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2


def lm_head_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """``[live_seqs, hidden] @ [hidden, vocab]``: the head's weights are
    read once, the activations in and the logits out once each."""
    seqs = shapes.get("live_seqs")
    if not seqs:
        return None
    h, v = shapes["hidden_size"], shapes["vocab_size"]
    return {
        "bytes": (h * v + seqs * (h + v)) * BF16,
        "flops": 2.0 * seqs * h * v,
    }
