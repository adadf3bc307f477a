"""Mixtral's sizes -> the program's config object (``models/moe.py``).
Imported in the engine child and by ``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict

from fmabench import spec

_trunk = spec.sibling_part(__file__, "mistral", "program")


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import moe

    return moe.MoeConfig(
        num_experts=d["num_experts"],
        experts_per_token=d["experts_per_token"], **_trunk.common(d),
    )
