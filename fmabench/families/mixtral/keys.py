"""Mixtral (``MixtralForCausalLM``): Mistral's trunk with, in every layer, a
top-k router over E SwiGLU experts whose top-k scores are renormalised by a
softmax. Keys as the published ``config.json`` has them. Standard library
only: the parent of a run loads this file."""

from __future__ import annotations

from typing import Any, Dict

from fmabench import spec

_trunk = spec.sibling_part(__file__, "mistral", "keys")

carried = _trunk.carried + ("router_aux_loss_coef",)
#: depth alone: the program's expert layer holds every expert (it has no
#: layer that is told which experts are here), so their number is a width
reducible = _trunk.reducible
kv_bytes = _trunk.kv_bytes


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    return {
        **_trunk.dims(config),
        "num_experts": int(config["num_local_experts"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
    }


def param_count(d: Dict[str, Any]) -> int:
    h, f, experts = d["hidden_size"], d["intermediate_size"], d["num_experts"]
    return _trunk.param_count(d, ffn=h * experts + experts * 3 * h * f)
