"""Mistral (``MistralForCausalLM``): a dense pre-norm decoder. RMSNorm,
grouped-query attention with rotate-half RoPE over a full causal mask,
SwiGLU; untied embedding and head. Keys as the published ``config.json``
has them. Standard library only: the parent of a run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

#: copied from the source for the record; nothing served depends on them
carried = ("architectures", "model_type", "torch_dtype")
#: what may stand in ``reduced``: depth alone (the program divides neither
#: the vocabulary nor the heads of a layer without a mesh); every other key
#: ``dims`` looks at is a width
reducible = ("num_hidden_layers",)


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs, from HF ``config.json`` keys. A
    stated value that this family would serve as something else is refused."""
    heads = int(config["num_attention_heads"])
    hidden = int(config["hidden_size"])
    max_context = int(config["assumed"]["max_context"])
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("this family's FFN is SwiGLU: hidden_act must be silu")
    if config.get("tie_word_embeddings", False):
        raise ValueError("this family's head is untied from its embedding")
    window = config.get("sliding_window")
    if window is not None and int(window) < max_context:
        raise ValueError(
            f"sliding_window {window} is shorter than assumed.max_context "
            f"{max_context}: this family computes the full causal mask"
        )
    if max_context > int(config.get("max_position_embeddings") or max_context):
        raise ValueError("assumed.max_context exceeds max_position_embeddings")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": hidden,
        "num_layers": int(config["num_hidden_layers"]),
        "num_heads": heads,
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or hidden // heads),
        "intermediate_size": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": max_context,
    }


def param_count(d: Dict[str, Any], ffn: Optional[int] = None) -> int:
    """Parameters of the whole model; ``ffn`` is one layer's FFN where it is
    not the dense SwiGLU (the family that shares this trunk gives its own)."""
    h = d["hidden_size"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    attn = h * q + 2 * h * kv + q * h + 2 * h
    if ffn is None:
        ffn = 3 * h * d["intermediate_size"]
    return d["num_layers"] * (attn + ffn) + 2 * d["vocab_size"] * h + h


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the paged KV pool: K and V in bfloat16, one page shape in
    every layer."""
    return (2 * d["num_layers"] * num_pages * page_size
            * d["num_kv_heads"] * d["head_dim"] * 2)
