"""Ouro's sizes -> the program's config object (``models/llama.py``: the
dense trunk with sandwich norms, its stack applied ``loop_steps`` times).
Imported in the engine child and by ``fmabench/rehearse.py`` only."""

from __future__ import annotations

from typing import Any, Dict

from fmabench import spec

_trunk = spec.sibling_part(__file__, "mistral", "program")


def build(d: Dict[str, Any]):
    from llm_d_fast_model_actuation_tpu.models import llama

    return llama.LlamaConfig(
        post_norms=True, loop_steps=d["loop_steps"], **_trunk.common(d)
    )
