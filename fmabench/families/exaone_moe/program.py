"""K-EXAONE's sizes -> the program's config object
(``models/exaone_moe.py``). Imported in the engine child and by the tests
only."""

from __future__ import annotations

from typing import Any, Dict


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import exaone_moe

    period = d["period"]
    windows = d["layer_windows"][:period]
    return exaone_moe.ExaoneMoeConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], head_dim=d["head_dim"],
        intermediate_size=d["expert_size"], rope_theta=d["rope_theta"],
        rms_eps=d["rms_eps"], max_seq_len=d["max_context"],
        num_experts=d["experts_held"],
        experts_per_token=d["experts_per_token"],
        router_width=d["router_width"], share_index=d["share_index"],
        routed_scaling=d["routed_scaling"],
        dense_max_rows=d["dense_max_rows"],
        window_pattern=tuple(d["window"] * w for w in windows),
        rope_pattern=tuple(bool(w) for w in windows),
        first_dense=d["first_dense"],
        dense_intermediate_size=d["dense_size"],
        shared_experts=d["shared_experts"], nextn_layers=d["nextn_layers"],
    )
