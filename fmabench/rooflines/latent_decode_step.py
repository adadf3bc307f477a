"""Latent decode attention in its absorbed form for one latent layer of one
decode step: every live sequence's context is read ONCE, the row a token
holds serving as key (all of it) and as value (its first kv_lora_rank
values), plus q in and o out.

A token's row is counted as the algorithm needs it: ``latent_dim`` =
kv_lora_rank + qk_rope_head_dim values (512 + 64 = 576, 1,152 B in
bfloat16), not the ``latent_stored`` lanes it is laid out in (640: the 64
shared key values keep a 128-lane tile). A kernel that reads the padding
moves a ninth more than this and reads a lower share for it. The query rows
are the absorbed ones, ``latent_dim`` wide a head, the outputs
``kv_lora_rank`` wide a head. 32 query rows a token share each row, so the
arithmetic is 2 x 32 x (576 + 512) flops a cached token, 61 flops a byte:
under the ridge of 240, the bytes decide; the flops are given for
completeness.

``live_kv_tokens`` is the mean, over the profiled stretch, of the sum of the
context lengths of the sequences that were decoding, and ``live_seqs`` their
mean number, both from the client's records.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16 = 2


def latent_decode_step(shapes: Dict[str, Any]) -> Optional[Dict[str, float]]:
    tokens = shapes.get("live_kv_tokens")
    seqs = shapes.get("live_seqs")
    if not tokens or not seqs or not shapes.get("latent_dim"):
        return None
    heads, row, rank = shapes["num_heads"], shapes["latent_dim"], shapes["kv_lora_rank"]
    return {
        "bytes": tokens * row * BF16 + seqs * heads * (row + rank) * BF16,
        "flops": 2.0 * tokens * heads * (row + rank),
    }
