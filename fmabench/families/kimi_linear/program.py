"""Kimi-Linear's sizes -> the program's config object
(``models/kimi_linear.py``). Imported in the engine child and by
``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import kimi_linear

    return kimi_linear.KimiLinearConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_heads"],
        head_dim=d["hidden_size"] // d["num_heads"],
        intermediate_size=d["expert_size"], rms_eps=d["rms_eps"],
        max_seq_len=d["max_context"], num_experts=d["experts_held"],
        experts_per_token=d["experts_per_token"],
        router_width=d["router_width"], share_index=d["share_index"],
        routed_scaling=d["routed_scaling"],
        layer_kinds=tuple(d["layer_kinds"]), first_dense=d["first_dense"],
        dense_intermediate_size=d["dense_size"],
        shared_experts=d["shared_experts"], kda_heads=d["kda_heads"],
        kda_head_dim=d["kda_head_dim"], conv_kernel=d["conv_kernel"],
        kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
    )
