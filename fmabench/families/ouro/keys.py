"""Ouro (``OuroForCausalLM``, ByteDance): a looped decoder. ONE stack of
``num_hidden_layers`` pre-norm layers with sandwich norms (multi-head
attention with rotate-half RoPE over the full causal mask, SwiGLU) is applied
``total_ut_steps`` times a token; the final norm closes every pass and its
output opens the next; pass u's layer l keeps its own K and V, so a token
holds ``total_ut_steps x num_hidden_layers`` cache layers. An exit gate
(Linear hidden -> 1) gives each pass an exit probability; at the published
``early_exit_threshold`` of 1 no token leaves before the last pass. Untied
embedding and head. Keys as the published ``config.json`` has them. Standard
library only: the parent of a run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict

#: copied from the source for the record; nothing served depends on them
#: (``sliding_window`` and ``max_window_layers`` mean nothing while
#: ``use_sliding_window`` is false, which ``dims`` holds the file to)
carried = ("model_type", "sliding_window", "max_window_layers")
#: what may stand in ``reduced``: depth alone. The passes are the mechanism
#: and every other key ``dims`` looks at is a width.
reducible = ("num_hidden_layers",)


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs. A stated value that this family
    would serve as something else is refused."""
    depth = int(config["num_hidden_layers"])
    passes = int(config["total_ut_steps"])
    max_context = int(config["assumed"]["max_context"])
    if max_context > int(config["max_position_embeddings"]):
        raise ValueError("assumed.max_context exceeds max_position_embeddings")
    if passes < 1:
        raise ValueError("total_ut_steps: at least one pass")
    if float(config["early_exit_threshold"]) < 1:
        raise ValueError(
            f"early_exit_threshold {config['early_exit_threshold']}: this "
            "family runs every pass for every token; adaptive exit below a "
            "threshold of 1 is not built"
        )
    kinds = list(config["layer_types"])
    if len(kinds) < depth or any(k != "full_attention" for k in kinds):
        raise ValueError(
            f"layer_types must give full_attention for each of {depth} layers"
        )
    if config["use_sliding_window"]:
        raise ValueError("use_sliding_window true: this family computes the full causal mask")
    if config["rope_scaling"] is not None:
        raise ValueError("this family's RoPE is unscaled: rope_scaling must be null")
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is untied from its embedding")
    if config["hidden_act"] != "silu":
        raise ValueError("this family's FFN is SwiGLU: hidden_act must be silu")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "num_layers": depth,
        "loop_steps": passes,
        "cache_layers": passes * depth,
        "num_heads": int(config["num_attention_heads"]),
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "intermediate_size": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": max_context,
    }


def param_count(d: Dict[str, Any]) -> int:
    """Parameters held (each layer once, however often it is applied): four
    projections, SwiGLU and FOUR norms a layer; embedding, head, the final
    norm and the exit gate's weight and bias."""
    h = d["hidden_size"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    layer = h * q + 2 * h * kv + q * h + 3 * h * d["intermediate_size"] + 4 * h
    return d["num_layers"] * layer + 2 * d["vocab_size"] * h + h + (h + 1)


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the paged KV pool: K and V in bfloat16, a cache layer for
    each (pass, layer)."""
    return (2 * d["cache_layers"] * num_pages * page_size
            * d["num_kv_heads"] * d["head_dim"] * 2)
