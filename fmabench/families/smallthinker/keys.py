"""SmallThinker (``SmallThinkerForCausalLM``, PowerInfer): a pre-norm
decoder whose layers follow a published pattern. ``sliding_window_layout``
and ``rope_layout`` give, per layer, 1 for a sliding window of
``sliding_window_size`` tokens with rotate-half RoPE and 0 for the full
causal mask with no positional encoding; every layer's FFN is a top-k
router (read from the layer's input, before attention) over primary experts
with a ReLU gate, no shared expert; untied embedding and head. Keys as the
published ``config.json`` has them. Standard library only: the parent of a
run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict, List

#: copied from the source for the record; nothing served depends on them
carried = ("model_name", "architectures", "model_type", "torch_dtype")
#: what may stand in ``reduced``: depth alone, in whole periods of the
#: layer pattern. The program's expert layer holds every expert and its
#: head the whole vocabulary, so their numbers are widths.
reducible = ("num_hidden_layers",)


def _layout(config: Dict[str, Any], key: str, depth: int) -> List[int]:
    """A published per-layer layout, cut to the depth held: the file keeps
    the source's list whole and the first ``depth`` entries are served."""
    layout = [int(x) for x in config[key]]
    if len(layout) < depth or any(x not in (0, 1) for x in layout):
        raise ValueError(f"{key} must give 0 or 1 for each of {depth} layers")
    return layout[:depth]


def _period(rows: List[tuple]) -> int:
    for p in range(1, len(rows) + 1):
        if len(rows) % p == 0 and rows == rows[:p] * (len(rows) // p):
            return p
    raise AssertionError("a list is a period of itself")


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs. A stated value that this family
    would serve as something else is refused."""
    depth = int(config["num_hidden_layers"])
    windows = _layout(config, "sliding_window_layout", depth)
    ropes = _layout(config, "rope_layout", depth)
    max_context = int(config["assumed"]["max_context"])
    if max_context > int(config["max_position_embeddings"]):
        raise ValueError("assumed.max_context exceeds max_position_embeddings")
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError(
            "moe_primary_router_apply_softmax false (sigmoid scores): this "
            "family's router weights its experts by a softmax"
        )
    if not config["norm_topk_prob"]:
        raise ValueError(
            "norm_topk_prob false: this family renormalises the scores of "
            "the experts it keeps"
        )
    if config.get("tie_word_embeddings", False):
        raise ValueError("this family's head is untied from its embedding")
    if config.get("rope_scaling") is not None:
        raise ValueError("this family's RoPE is unscaled: rope_scaling must be null")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "num_layers": depth,
        "num_heads": int(config["num_attention_heads"]),
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert_size": int(config["moe_ffn_hidden_size"]),
        "num_experts": int(config["moe_num_primary_experts"]),
        "experts_per_token": int(config["moe_num_active_primary_experts"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": max_context,
        "window": int(config["sliding_window_size"]),
        "window_layout": windows,
        "rope_layout": ropes,
        "period": _period(list(zip(windows, ropes))),
    }


def param_count(d: Dict[str, Any]) -> int:
    h = d["hidden_size"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    attn = h * q + 2 * h * kv + q * h + 2 * h
    experts = d["num_experts"]
    ffn = h * experts + experts * 3 * h * d["expert_size"]
    return d["num_layers"] * (attn + ffn) + 2 * d["vocab_size"] * h + h


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the paged KV pool: K and V in bfloat16 for the
    full-attention layers alone. The window layers' rings (``ring_bytes``)
    come on top; their size follows from engine options this signature is
    not given."""
    full = d["num_layers"] - sum(d["window_layout"])
    return 2 * full * num_pages * page_size * d["num_kv_heads"] * d["head_dim"] * 2


def ring_bytes(d: Dict[str, Any], max_batch: int, segment: int) -> int:
    """Bytes of the window layers' rings: a ring of window + one prefill
    segment for each of ``max_batch`` sequences, K and V in bfloat16."""
    ring = min(d["window"] + segment, d["max_context"])
    return (2 * sum(d["window_layout"]) * max_batch * ring
            * d["num_kv_heads"] * d["head_dim"] * 2)
