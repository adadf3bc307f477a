"""Nemotron-H's sizes -> the program's config object
(``models/nemotron_h.py``). Imported in the engine child and by
``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import nemotron_h

    return nemotron_h.NemotronHConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], head_dim=d["head_dim"],
        intermediate_size=d["expert_size"], rms_eps=d["rms_eps"],
        max_seq_len=d["max_context"], num_experts=d["experts_held"],
        experts_per_token=d["experts_per_token"],
        router_width=d["router_width"], share_index=d["share_index"],
        routed_scaling=d["routed_scaling"],
        dense_max_rows=d["dense_max_rows"],
        layer_kinds=tuple(d["layer_kinds"]),
        latent_size=d["moe_latent_size"],
        shared_intermediate_size=d["shared_size"],
        mamba_heads=d["mamba_heads"], mamba_head_dim=d["mamba_head_dim"],
        ssm_groups=d["ssm_groups"], ssm_state_size=d["ssm_state_size"],
        conv_kernel=d["conv_kernel"], chunk_size=d["chunk_size"],
    )
