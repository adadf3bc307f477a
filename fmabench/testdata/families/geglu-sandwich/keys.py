"""A made-up family, the worked example of ``fmabench/README.md`` ("A
family") and the proof that the harness takes an architecture as files: a
dense pre-norm decoder whose FFN gate is GELU (tanh), whose q and k get a
per-head RMSNorm before RoPE, and whose attention and FFN outputs are
normalised before each residual. The program runs all three already
(``LlamaConfig.hidden_activation``, ``qk_norm``, ``post_norms``); neither
family under ``fmabench/families/`` can say them. Its FFN width stands
under a key of its own, ``ffn_hidden_size``. It exists under ``testdata/``
only and is no benchmark configuration. Standard library only.
"""

from __future__ import annotations

from typing import Any, Dict

carried = ("architectures", "torch_dtype")
reducible = ("num_hidden_layers",)


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    # the three mechanisms are what this family IS: a file that switches one
    # off describes another model
    if config["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError("this family's FFN gate is the tanh GELU")
    if not (config["qk_norm"] and config["sandwich_norms"]):
        raise ValueError("this family has q/k norms and output norms")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "num_layers": int(config["num_hidden_layers"]),
        "num_heads": int(config["num_attention_heads"]),
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "ffn_size": int(config["ffn_hidden_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": int(config["assumed"]["max_context"]),
    }


def param_count(d: Dict[str, Any]) -> int:
    h, hd = d["hidden_size"], d["head_dim"]
    q, kv = d["num_heads"] * hd, d["num_kv_heads"] * hd
    norms = 4 * h + 2 * hd  # attn, mlp, the two output norms; q and k
    layer = h * q + 2 * h * kv + q * h + 3 * h * d["ffn_size"] + norms
    return d["num_layers"] * layer + 2 * d["vocab_size"] * h + h


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    return (2 * d["num_layers"] * num_pages * page_size
            * d["num_kv_heads"] * d["head_dim"] * 2)
