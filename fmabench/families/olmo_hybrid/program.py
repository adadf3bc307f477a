"""Olmo-Hybrid's sizes -> the program's config object
(``models/olmo_hybrid.py``). Imported in the engine child and by
``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import olmo_hybrid

    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], head_dim=d["head_dim"],
        intermediate_size=d["intermediate_size"], rms_eps=d["rms_eps"],
        max_seq_len=d["max_context"], layer_kinds=tuple(d["layer_kinds"]),
        linear_heads=d["linear_heads"], linear_key_dim=d["linear_key_dim"],
        linear_value_dim=d["linear_value_dim"], conv_kernel=d["conv_kernel"],
        allow_neg_eigval=d["allow_neg_eigval"],
    )
