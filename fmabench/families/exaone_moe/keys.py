"""K-EXAONE (``model_type: exaone_moe``, LGAI-EXAONE): a decoder whose layers
differ in attention kind and in FFN kind, with a multi-token-prediction
module. ``layer_types`` / ``sliding_windows`` name each layer's attention
(``sliding_attention`` over the last ``sliding_window`` positions with
rotary, ``full_attention`` over the whole context without positional
encoding: ``assumed.global_nope``), ``mlp_layer_types`` its FFN (``dense``:
SwiGLU of ``intermediate_size``; ``sparse``: a shared expert plus the top
``num_experts_per_tok`` of the routed experts by sigmoid scores, renormalised
and scaled by ``routed_scaling_factor``). The block has norms on each
sub-layer's OUTPUT and none on its input, and normalises q and k a head
(``assumed.block``, ``assumed.qk_norm``). ``num_nextn_predict_layers`` blocks
of kind ``mtp_layer_types`` draft the token after next (``assumed.mtp``).
``num_experts`` is how many of the router's ``published.num_experts`` outputs
are HELD here (one chip's share, ``assumed.share_index``), ``vocab_size`` the
rows of the vocabulary held. Keys as the published ``config.json`` has them.
Standard library only: the parent of a run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict, List

#: copied from the source for the record; nothing served depends on them:
#: ``sliding_window_pattern`` says again what ``layer_types`` says layer by
#: layer (checked against it below)
carried = ("model_type",)
#: what may stand in ``reduced``: depth in whole periods, the experts held of
#: the router's width, the rows of the vocabulary held
reducible = ("num_hidden_layers", "num_experts", "vocab_size")

KINDS = {"sliding_attention": 1, "full_attention": 0}


def _refuse(config: Dict[str, Any]) -> None:
    """A stated value that this family would serve as something else."""
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError(
            "n_group / topk_group != 1: this family's router keeps the top k "
            "of ALL its outputs, one group"
        )
    if config["scoring_func"] != "sigmoid":
        raise ValueError("this family's router scores are sigmoids")
    if not config["norm_topk_prob"]:
        raise ValueError(
            "norm_topk_prob false: this family divides the kept scores by "
            "their sum"
        )
    if int(config["first_k_dense_replace"]) != 1:
        raise ValueError(
            "first_k_dense_replace != 1: this family has one leading dense layer"
        )
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is untied from its embedding")
    if config["hidden_act"] != "silu":
        raise ValueError("this family's FFNs are SwiGLU: hidden_act must be silu")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default" or set(rope) - {
        "rope_theta", "rope_type"
    }:
        raise ValueError(
            f"rope_parameters {rope}: this family's rotary is the plain one"
        )
    if int(config["num_nextn_predict_layers"]) != 1:
        raise ValueError(
            "num_nextn_predict_layers != 1: this family has one prediction "
            "module"
        )
    if list(config["mtp_layer_types"]) != ["full_attention"] or [
        int(w) for w in config["mtp_sliding_windows"]
    ] != [0]:
        raise ValueError(
            "mtp_layer_types / mtp_sliding_windows: the prediction module is "
            "one full-attention block"
        )


def _layers(config: Dict[str, Any], depth: int) -> List[int]:
    """Per served layer, 1 for a window layer: ``layer_types`` and
    ``sliding_windows`` (kept whole from the source; the first ``depth``
    are served) must agree, in whole periods of ``sliding_window_pattern``."""
    kinds, windows = config["layer_types"], config["sliding_windows"]
    window = int(config["sliding_window"])
    pattern = str(config["sliding_window_pattern"])
    if len(kinds) < depth or len(windows) < depth or depth % len(pattern):
        raise ValueError(
            f"{depth} layers: layer_types and sliding_windows must name each, "
            f"in whole periods of {pattern!r}"
        )
    out = []
    for l in range(depth):
        if kinds[l] not in KINDS:
            raise ValueError(f"layer_types[{l}] {kinds[l]!r}: not a kind served")
        is_window = KINDS[kinds[l]]
        if int(windows[l]) != window * is_window or (
            pattern[l % len(pattern)] == "L"
        ) != bool(is_window):
            raise ValueError(
                f"layer {l}: layer_types, sliding_windows, sliding_window and "
                "sliding_window_pattern disagree"
            )
        out.append(is_window)
    if out != out[: len(pattern)] * (depth // len(pattern)):
        raise ValueError("layer_types does not repeat its period")
    return out


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs."""
    _refuse(config)
    depth = int(config["num_hidden_layers"])
    layout = _layers(config, depth)
    mlp = list(config["mlp_layer_types"])[:depth]
    if mlp != ["dense"] + ["sparse"] * (depth - 1):
        raise ValueError(
            "mlp_layer_types: one leading dense layer, every later one sparse"
        )
    held = int(config["num_experts"])
    width = int((config.get("published") or {}).get("num_experts", held))
    share = int(config["assumed"].get("share_index", 0))
    if held < 1 or (share + 1) * held > width:
        raise ValueError(
            f"share {share} of {held} experts lies outside the router's {width}"
        )
    max_context = int(config["assumed"]["max_context"])
    if max_context > int(config["max_position_embeddings"]):
        raise ValueError("assumed.max_context exceeds max_position_embeddings")
    period = len(str(config["sliding_window_pattern"]))
    nextn = int(config["num_nextn_predict_layers"])
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "num_layers": depth,
        "period": period,
        "num_heads": int(config["num_attention_heads"]),
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        # one entry a call of the decode kernel in a step: the main stack's
        # layers, then the prediction module's full-attention block
        # (rooflines/paged_decode_step_windowed.py takes the mean over it)
        "window_layout": layout + [0] * nextn,
        "layer_windows": layout,
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "first_dense": 1,
        "expert_layers": depth - 1,
        "nextn_layers": nextn,
        "dense_size": int(config["intermediate_size"]),
        "expert_size": int(config["moe_intermediate_size"]),
        "num_experts": held,
        "experts_held": held,
        "router_width": width,
        "share_index": share,
        "experts_per_token": int(config["num_experts_per_tok"]),
        "shared_experts": int(config["num_shared_experts"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
        # a serving choice, not the model's: programs of this many rows or
        # fewer compute every held expert on every row
        # (models/moe.py:held_dense_ffn)
        "dense_max_rows": int(config["assumed"]["dense_max_rows"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": max_context,
    }


def attention_params(d: Dict[str, Any]) -> int:
    """q, k, v, o; the two head norms; the two output norms."""
    h = d["hidden_size"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    return h * q + 2 * h * kv + q * h + 2 * d["head_dim"] + 2 * h


def expert_params(d: Dict[str, Any]) -> int:
    return 3 * d["hidden_size"] * d["expert_size"]


def expert_layer_ffn_params(d: Dict[str, Any]) -> int:
    """The held experts, the shared expert, the router over its whole width
    and its selection biases."""
    return ((d["experts_held"] + d["shared_experts"]) * expert_params(d)
            + d["hidden_size"] * d["router_width"] + d["router_width"])


def module_params(d: Dict[str, Any]) -> int:
    """The prediction module: one block with experts, the 2h -> h projection
    and three norms (embedding, hidden, final)."""
    h = d["hidden_size"]
    return d["nextn_layers"] * (
        attention_params(d) + expert_layer_ffn_params(d) + 2 * h * h + 3 * h
    )


def param_count(d: Dict[str, Any]) -> int:
    h = d["hidden_size"]
    return (d["num_layers"] * attention_params(d)
            + d["first_dense"] * 3 * h * d["dense_size"]
            + d["expert_layers"] * expert_layer_ffn_params(d)
            + module_params(d)
            + 2 * d["vocab_size"] * h + h)


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the paged KV pool as served with the module drafting: K and
    V in bfloat16 for the full-attention layers and the prediction module's
    block. The window layers' rings (``ring_bytes``) come on top; their size
    follows from engine options this signature is not given."""
    full = d["num_layers"] - sum(d["layer_windows"]) + d["nextn_layers"]
    return 2 * full * num_pages * page_size * d["num_kv_heads"] * d["head_dim"] * 2


def ring_bytes(d: Dict[str, Any], max_batch: int, segment: int) -> int:
    """Bytes of the window layers' rings: a ring of window + one prefill
    segment for each of ``max_batch`` sequences, K and V in bfloat16."""
    ring = min(d["window"] + segment, d["max_context"])
    return (2 * sum(d["layer_windows"]) * max_batch * ring
            * d["num_kv_heads"] * d["head_dim"] * 2)
