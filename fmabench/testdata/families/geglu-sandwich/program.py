"""The example family's sizes -> the program's config object: the shared
``LlamaConfig`` with its GELU gate, q/k norms and output norms switched on.
Imported in the engine child and by ``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], head_dim=d["head_dim"],
        intermediate_size=d["ffn_size"], rope_theta=d["rope_theta"],
        rms_eps=d["rms_eps"], max_seq_len=d["max_context"],
        hidden_activation="gelu", qk_norm=True, post_norms=True,
    )
