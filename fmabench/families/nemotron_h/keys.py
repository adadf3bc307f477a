"""Nemotron-H (``model_type: nemotron_h``, nvidia; Nemotron-3-Super): a
pre-norm decoder whose layers are ONE sub-layer each, ``x + Mixer(RMSNorm(x))``.
``hybrid_override_pattern`` names every layer's mixer, one character a layer:
``M`` a Mamba-2 layer (``mamba_num_heads`` heads of ``mamba_head_dim``
channels, ``n_groups`` groups of ``ssm_state_size`` state channels, a float32
matrix a head as the layer's whole sequence state, behind a depthwise causal
convolution of ``conv_kernel`` taps WITH a bias over x, B and C together), ``E``
an expert layer (a shared expert of ``moe_shared_expert_intermediate_size``
on the layer's input; the top ``num_experts_per_tok`` of the routed experts by
sigmoid scores, renormalised and scaled by ``routed_scaling_factor``, each two
matrices of ``moe_intermediate_size`` with the squared ReLU between them,
working in a ``moe_latent_size``-wide projection of the input), ``*`` an
attention layer (``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``head_dim``, no positional encoding). The
pattern need not repeat; the file keeps the source's string whole and the
first ``num_hidden_layers`` characters are served. ``n_routed_experts`` is how
many of the router's ``published.n_routed_experts`` outputs are HELD here (one
chip's share, ``assumed.share_index``), ``vocab_size`` the rows of the
vocabulary held. Keys as the published ``config.json`` has them. Standard
library only: the parent of a run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict

#: copied from the source for the record; nothing served depends on them: no
#: layer has rotary or a dense FFN of intermediate_size, the multi-token
#: prediction module is not served (assumed.mtp), and the rest are switches of
#: the published implementation (kernels, initialisation, what a forward
#: returns, where the residual is kept) that change no equation
carried = (
    "model_type", "rope_theta", "partial_rotary_factor", "intermediate_size",
    "num_nextn_predict_layers", "mtp_hybrid_override_pattern",
    "rescale_prenorm_residual", "residual_in_fp32", "use_mamba_kernels",
    "num_logits_to_keep", "moe_shared_expert_overlap", "time_step_floor",
    "time_step_max", "time_step_min",
)
#: what may stand in ``reduced``: depth (a prefix of the pattern that is a
#: whole stretch up to an attention layer's period), the experts held of the
#: router's width, the rows of the vocabulary held
reducible = ("num_hidden_layers", "n_routed_experts", "vocab_size")

KINDS = {"M": "ssm", "E": "moe", "*": "attn"}


def _refuse(config: Dict[str, Any]) -> None:
    """A stated value that this family would serve as something else."""
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError(
            "n_group / topk_group != 1: this family's router keeps the top k "
            "of ALL its outputs, one group"
        )
    if config["sliding_window"] is not None:
        raise ValueError(
            "sliding_window is not null: this family's attention layers see "
            "their whole context"
        )
    for flag in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias"):
        if config[flag]:
            raise ValueError(
                f"{flag} true: this family's only bias is the convolution's"
            )
    if not config["use_conv_bias"]:
        raise ValueError("use_conv_bias false: this family's convolution has a bias")
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is untied from its embedding")
    if config["mlp_hidden_act"] != "relu2":
        raise ValueError("this family's experts use the squared ReLU: mlp_hidden_act relu2")
    if config["mamba_hidden_act"] != "silu":
        raise ValueError("this family's convolution and gate use SiLU: mamba_hidden_act silu")
    if not config["norm_topk_prob"]:
        raise ValueError(
            "norm_topk_prob false: this family divides the kept scores by their sum"
        )


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs."""
    _refuse(config)
    depth = int(config["num_hidden_layers"])
    pattern = str(config["hybrid_override_pattern"])
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern has {unknown}: this family has Mamba-2 "
            "(M), expert (E) and attention (*) layers"
        )
    if len(pattern) < depth or depth < 1:
        raise ValueError(
            f"hybrid_override_pattern names {len(pattern)} layers, "
            f"num_hidden_layers is {depth}"
        )
    kinds = pattern[:depth]
    hidden = int(config["hidden_size"])
    heads, head_dim = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    groups, state = int(config["n_groups"]), int(config["ssm_state_size"])
    if heads * head_dim != int(config["expand"]) * hidden:
        raise ValueError(
            f"mamba_num_heads x mamba_head_dim = {heads * head_dim} is not "
            f"expand x hidden_size = {int(config['expand']) * hidden}"
        )
    if heads % groups:
        raise ValueError(
            f"mamba_num_heads {heads} is no multiple of n_groups {groups}"
        )
    kernel = int(config["conv_kernel"])
    if kernel < 2:
        raise ValueError("conv_kernel: a convolution of at least 2 taps")
    held = int(config["n_routed_experts"])
    width = int((config.get("published") or {}).get("n_routed_experts", held))
    share = int(config["assumed"].get("share_index", 0))
    if held < 1 or (share + 1) * held > width:
        raise ValueError(
            f"share {share} of {held} experts lies outside the router's {width}"
        )
    max_context = int(config["assumed"]["max_context"])
    if max_context > int(config["max_position_embeddings"]):
        raise ValueError("assumed.max_context exceeds max_position_embeddings")
    if float(config["norm_eps"]) != float(config["layer_norm_epsilon"]):
        raise ValueError(
            "norm_eps != layer_norm_epsilon: this family's norms share one eps"
        )
    inner = heads * head_dim
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": hidden,
        "num_layers": depth,
        "layer_kinds": kinds,
        "ssm_layers": kinds.count("M"),
        "expert_layers": kinds.count("E"),
        "attn_layers": kinds.count("*"),
        "num_heads": int(config["num_attention_heads"]),
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "mamba_heads": heads,
        "mamba_head_dim": head_dim,
        "ssm_groups": groups,
        "ssm_state_size": state,
        "conv_kernel": kernel,
        "chunk_size": int(config["chunk_size"]),
        "inner_size": inner,
        "conv_dim": inner + 2 * groups * state,
        "in_proj_size": 2 * inner + 2 * groups * state + heads,
        "expert_size": int(config["moe_intermediate_size"]),
        "moe_latent_size": int(config["moe_latent_size"]),
        "shared_size": int(config["n_shared_experts"])
        * int(config["moe_shared_expert_intermediate_size"]),
        "experts_held": held,
        "router_width": width,
        "share_index": share,
        "experts_per_token": int(config["num_experts_per_tok"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
        "dense_max_rows": int(config["assumed"]["dense_max_rows"]),
        "rms_eps": float(config["norm_eps"]),
        "max_context": max_context,
    }


def mamba_layer_params(d: Dict[str, Any]) -> int:
    """W_in; the convolution's taps and bias; A_log, D and dt_bias a head; the
    gated norm's weight; W_out."""
    h = d["hidden_size"]
    return (h * d["in_proj_size"] + (d["conv_kernel"] + 1) * d["conv_dim"]
            + 3 * d["mamba_heads"] + d["inner_size"] + d["inner_size"] * h)


def attention_layer_params(d: Dict[str, Any]) -> int:
    h = d["hidden_size"]
    return (2 * h * d["num_heads"] * d["head_dim"]
            + 2 * h * d["num_kv_heads"] * d["head_dim"])


def expert_params(d: Dict[str, Any]) -> int:
    """One routed expert: two matrices in the latent space."""
    return 2 * d["moe_latent_size"] * d["expert_size"]


def expert_layer_params(d: Dict[str, Any]) -> int:
    """The held experts, the shared expert, the projections into and out of
    the latent space, the router over its whole width and its selection
    biases."""
    h = d["hidden_size"]
    return (d["experts_held"] * expert_params(d) + 2 * h * d["shared_size"]
            + 2 * h * d["moe_latent_size"]
            + h * d["router_width"] + d["router_width"])


def param_count(d: Dict[str, Any]) -> int:
    h = d["hidden_size"]
    return (d["num_layers"] * h
            + d["ssm_layers"] * mamba_layer_params(d)
            + d["expert_layers"] * expert_layer_params(d)
            + d["attn_layers"] * attention_layer_params(d)
            + 2 * d["vocab_size"] * h + h)


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the K and V pages, bfloat16: the attention layers alone keep
    any. The Mamba-2 layers' recurrent state (``state_bytes``) comes on top;
    its size follows from ``--max-batch``, which this signature is not given."""
    return (d["attn_layers"] * num_pages * page_size
            * 2 * d["num_kv_heads"] * d["head_dim"] * 2)


def state_bytes_per_slot_layer(d: Dict[str, Any]) -> int:
    """What one slot holds for one Mamba-2 layer: h [heads, head_dim, state]
    in float32 and the convolution's last kernel - 1 inputs in bfloat16."""
    h = d["mamba_heads"] * d["mamba_head_dim"] * d["ssm_state_size"] * 4
    return h + (d["conv_kernel"] - 1) * d["conv_dim"] * 2


def state_bytes(d: Dict[str, Any], max_batch: int) -> int:
    return d["ssm_layers"] * max_batch * state_bytes_per_slot_layer(d)
