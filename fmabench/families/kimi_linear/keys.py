"""Kimi-Linear (``model_type: kimi_linear``, moonshotai; arXiv:2510.26692): a
pre-norm decoder whose layers differ in mixer kind and in FFN kind.
``linear_attn_config`` lists, 1-based, the layers of Kimi Delta Attention
(``kda_layers``: the gated delta rule with a decay a channel, a float32
matrix a head as the layer's whole sequence state, behind depthwise causal
convolutions of ``short_conv_kernel_size`` taps) and of latent attention
(``full_attn_layers``: MLA, whose cache is the compressed ``kv_lora_rank`` +
``qk_rope_head_dim`` values a token, with no positional encoding:
``mla_use_nope``). The first ``first_k_dense_replace`` layers' FFN is SwiGLU
of ``intermediate_size``; every later layer is a shared expert plus the top
``num_experts_per_token`` of the routed experts by sigmoid scores, renormalised
and scaled by ``routed_scaling_factor``. ``num_experts`` is how many of the
router's ``published.num_experts`` outputs are HELD here (one chip's share,
``assumed.share_index``), ``vocab_size`` the rows of the vocabulary held.
Keys as the published ``config.json`` has them. Standard library only: the
parent of a run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict

#: copied from the source for the record; nothing served depends on them:
#: the latent layers have no rotary (mla_use_nope), and no layer has KV heads
#: or a head of hidden_size / num_attention_heads
carried = ("model_type", "rope_theta", "head_dim", "num_key_value_heads")
#: what may stand in ``reduced``: depth in whole periods, the experts held of
#: the router's width, the rows of the vocabulary held
reducible = ("num_hidden_layers", "num_experts", "vocab_size")

#: the one period this family's program and reference are built for
PERIOD = ("kda", "kda", "kda", "mla")
LANES = 128


def _refuse(config: Dict[str, Any]) -> None:
    """A stated value that this family would serve as something else."""
    if config["q_lora_rank"] is not None:
        raise ValueError(
            "q_lora_rank is not null: this family's latent layers project "
            "the query in one matrix"
        )
    if int(config["num_expert_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError(
            "num_expert_group / topk_group != 1: this family's router keeps "
            "the top k of ALL its outputs, one group"
        )
    if not config["mla_use_nope"]:
        raise ValueError(
            "mla_use_nope false: this family's latent layers have no rotary "
            "on either part of the key"
        )
    if int(config["first_k_dense_replace"]) != 1:
        raise ValueError(
            "first_k_dense_replace != 1: this family has one leading dense layer"
        )
    if int(config["moe_layer_freq"]) != 1:
        raise ValueError("moe_layer_freq != 1: every later layer has experts")
    if config["moe_router_activation_func"] != "sigmoid":
        raise ValueError("this family's router scores are sigmoids")
    if not config["moe_renormalize"]:
        raise ValueError(
            "moe_renormalize false: this family divides the kept scores by "
            "their sum"
        )
    if not config["use_grouped_topk"]:
        raise ValueError(
            "use_grouped_topk false: the selection bias (assumed) belongs to "
            "the grouped gate"
        )
    if config["rope_scaling"] is not None:
        raise ValueError("rope_scaling is not null: there is no rotary to scale")
    if int(config["num_nextn_predict_layers"]):
        raise ValueError("num_nextn_predict_layers != 0: no such head is built")
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is untied from its embedding")
    if config["hidden_act"] != "silu":
        raise ValueError("this family's FFNs are SwiGLU: hidden_act must be silu")


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs."""
    _refuse(config)
    depth = int(config["num_hidden_layers"])
    lin = config["linear_attn_config"]
    # the file keeps the source's two lists whole; the first ``depth`` layers
    # are served
    kda = {int(l) for l in lin["kda_layers"]}
    full = {int(l) for l in lin["full_attn_layers"]}
    kinds = ["kda" if l in kda else "mla" if l in full else "?"
             for l in range(1, depth + 1)]
    if kda & full or depth % len(PERIOD) or kinds != list(PERIOD) * (
        depth // len(PERIOD)
    ):
        raise ValueError(
            f"kda_layers / full_attn_layers must give whole periods of "
            f"{PERIOD} for each of {depth} layers; they give {kinds}"
        )
    held = int(config["num_experts"])
    width = int((config.get("published") or {}).get("num_experts", held))
    share = int(config["assumed"].get("share_index", 0))
    if held < 1 or (share + 1) * held > width:
        raise ValueError(
            f"share {share} of {held} experts lies outside the router's {width}"
        )
    max_context = int(config["assumed"]["max_context"])
    if max_context > int(config["model_max_length"]):
        raise ValueError("assumed.max_context exceeds model_max_length")
    rank, rope = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    if rank % LANES:
        raise ValueError(
            f"kv_lora_rank {rank}: the value of a latent row is a slice of "
            f"whole {LANES}-lane tiles"
        )
    heads, d = int(lin["num_heads"]), int(lin["head_dim"])
    kernel = int(lin["short_conv_kernel_size"])
    if kernel < 2:
        raise ValueError("short_conv_kernel_size: a convolution of at least 2 taps")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "num_layers": depth,
        "layer_kinds": list(PERIOD),
        "kda_layers": kinds.count("kda"),
        "mla_layers": kinds.count("mla"),
        "first_dense": 1,
        "expert_layers": depth - 1,
        "num_heads": int(config["num_attention_heads"]),
        "dense_size": int(config["intermediate_size"]),
        "expert_size": int(config["moe_intermediate_size"]),
        "num_experts": held,
        "experts_held": held,
        "router_width": width,
        "share_index": share,
        "experts_per_token": int(config["num_experts_per_token"]),
        "shared_experts": int(config["num_shared_experts"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
        "kda_heads": heads,
        "kda_head_dim": d,
        "conv_kernel": kernel,
        "conv_dim": 3 * heads * d,
        "kv_lora_rank": rank,
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": rope,
        "v_head_dim": int(config["v_head_dim"]),
        "latent_dim": rank + rope,
        "latent_stored": rank + -(-rope // LANES) * LANES,
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": max_context,
    }


def kda_mixer_params(d: Dict[str, Any]) -> int:
    """q, k, v projections and their three convolutions; the decay's and the
    output gate's two low-rank matrices each; w_b; A_log a head and dt_bias a
    channel; one norm weight of d; the output projection."""
    h, H, hd = d["hidden_size"], d["kda_heads"], d["kda_head_dim"]
    D = H * hd
    return (3 * h * D + 3 * d["conv_kernel"] * D + 2 * (h * hd + hd * D)
            + h * H + H + D + hd + D * h)


def mla_mixer_params(d: Dict[str, Any]) -> int:
    """q; the compression to latent_dim; the latent norm; W_kvb; o."""
    h, heads, rank = d["hidden_size"], d["num_heads"], d["kv_lora_rank"]
    q = heads * (d["qk_nope_head_dim"] + d["qk_rope_head_dim"])
    kvb = heads * (d["qk_nope_head_dim"] + d["v_head_dim"])
    return (h * q + h * d["latent_dim"] + rank + rank * kvb
            + heads * d["v_head_dim"] * h)


def expert_params(d: Dict[str, Any]) -> int:
    return 3 * d["hidden_size"] * d["expert_size"]


def expert_layer_ffn_params(d: Dict[str, Any]) -> int:
    """The held experts, the shared expert, the router over its whole width
    and its selection biases."""
    return ((d["experts_held"] + d["shared_experts"]) * expert_params(d)
            + d["hidden_size"] * d["router_width"] + d["router_width"])


def param_count(d: Dict[str, Any]) -> int:
    h = d["hidden_size"]
    return (d["num_layers"] * 2 * h
            + d["first_dense"] * 3 * h * d["dense_size"]
            + d["expert_layers"] * expert_layer_ffn_params(d)
            + d["kda_layers"] * kda_mixer_params(d)
            + d["mla_layers"] * mla_mixer_params(d)
            + 2 * d["vocab_size"] * h + h)


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the latent pages as laid out: ONE array of latent_stored
    bfloat16 lanes a token for the latent layers alone. The KDA layers'
    recurrent state (``state_bytes``) comes on top; its size follows from
    ``--max-batch``, which this signature is not given."""
    return d["mla_layers"] * num_pages * page_size * d["latent_stored"] * 2


def latent_bytes_counted(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """The same pages by the values the algorithm reads: latent_dim a token."""
    return d["mla_layers"] * num_pages * page_size * d["latent_dim"] * 2


def state_bytes_per_slot_layer(d: Dict[str, Any]) -> int:
    """What one slot holds for one KDA layer: S [heads, d, d] in float32 and
    the convolutions' last kernel - 1 inputs in bfloat16."""
    s = d["kda_heads"] * d["kda_head_dim"] * d["kda_head_dim"] * 4
    return s + (d["conv_kernel"] - 1) * d["conv_dim"] * 2


def state_bytes(d: Dict[str, Any], max_batch: int) -> int:
    return d["kda_layers"] * max_batch * state_bytes_per_slot_layer(d)
