"""SmallThinker's sizes -> the program's config object
(``models/smallthinker.py``). Imported in the engine child and by
``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import smallthinker

    p = d["period"]
    return smallthinker.SmallThinkerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        num_layers=d["num_layers"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], head_dim=d["head_dim"],
        intermediate_size=d["expert_size"], rope_theta=d["rope_theta"],
        rms_eps=d["rms_eps"], max_seq_len=d["max_context"],
        num_experts=d["num_experts"],
        experts_per_token=d["experts_per_token"],
        window_pattern=tuple(
            d["window"] if w else 0 for w in d["window_layout"][:p]
        ),
        rope_pattern=tuple(bool(r) for r in d["rope_layout"][:p]),
    )
