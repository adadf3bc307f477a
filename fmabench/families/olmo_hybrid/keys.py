"""Olmo-Hybrid (``model_type: olmo_hybrid``, allenai): a decoder whose layers
follow a published pattern of KINDS. ``layer_types`` gives, per layer,
``linear_attention`` (the gated delta rule, arXiv:2412.06464: a float32
matrix a head as the layer's whole sequence state, behind a depthwise causal
convolution of ``linear_conv_kernel_dim`` taps) or ``full_attention`` (causal
softmax attention, q and k RMS-normed, no positional encoding:
``rope_parameters.rope_theta`` is null). Every layer's norm sits on its
sub-layers' OUTPUT; the FFN is SwiGLU; embedding and head are untied. Keys as
the published ``config.json`` has them. Standard library only: the parent of
a run loads this file.
"""

from __future__ import annotations

from typing import Any, Dict

#: copied from the source for the record; nothing served depends on it
carried = ("model_type",)
#: what may stand in ``reduced``: depth alone, in whole periods of the layer
#: pattern; every other key ``dims`` looks at is a width
reducible = ("num_hidden_layers",)

KINDS = {"linear_attention": "linear", "full_attention": "full"}
#: the one period this family's program and reference are built for
PERIOD = ("linear", "linear", "linear", "full")


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the model code needs. A stated value that this family
    would serve as something else is refused."""
    depth = int(config["num_hidden_layers"])
    types = list(config["layer_types"])
    unknown = sorted(set(types) - set(KINDS))
    if unknown:
        raise ValueError(
            f"layer_types names {unknown}: this family has linear_attention "
            "and full_attention layers"
        )
    # the file keeps the source's list whole; the first ``depth`` are served
    kinds = [KINDS[t] for t in types][:depth]
    if len(types) < depth or depth % len(PERIOD) or kinds != list(PERIOD) * (
        depth // len(PERIOD)
    ):
        raise ValueError(
            f"layer_types must give whole periods of {PERIOD} for each of "
            f"{depth} layers"
        )
    heads = int(config["linear_num_key_heads"])
    if heads != int(config["linear_num_value_heads"]):
        raise ValueError(
            "linear_num_key_heads != linear_num_value_heads: this family's "
            "linear layers keep one state a head, keys and values alike"
        )
    max_context = int(config["assumed"]["max_context"])
    if max_context > int(config["max_position_embeddings"]):
        raise ValueError("assumed.max_context exceeds max_position_embeddings")
    hidden, q_heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    head_dim = int(config["assumed"]["head_dim"])
    if head_dim * q_heads != hidden:
        raise ValueError(
            "assumed.head_dim is not hidden_size / num_attention_heads: the "
            "source has no head_dim and this family takes that quotient"
        )
    if config["rope_parameters"] != {"rope_theta": None}:
        raise ValueError(
            "rope_parameters.rope_theta is not null: this family's "
            "full-attention layers have no positional encoding"
        )
    if config["attention_bias"]:
        raise ValueError("attention_bias true: this family's projections have no bias")
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is untied from its embedding")
    if config["hidden_act"] != "silu":
        raise ValueError("this family's FFN is SwiGLU: hidden_act must be silu")
    dk, dv = int(config["linear_key_head_dim"]), int(config["linear_value_head_dim"])
    kernel = int(config["linear_conv_kernel_dim"])
    if kernel < 2:
        raise ValueError("linear_conv_kernel_dim: a convolution of at least 2 taps")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": hidden,
        "num_layers": depth,
        "layer_kinds": list(PERIOD),
        "linear_layers": kinds.count("linear"),
        "full_layers": kinds.count("full"),
        "num_heads": q_heads,
        "num_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": head_dim,
        "intermediate_size": int(config["intermediate_size"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_context": max_context,
        "linear_heads": heads,
        "linear_key_dim": dk,
        "linear_value_dim": dv,
        "conv_kernel": kernel,
        "conv_dim": heads * (2 * dk + dv),
        "allow_neg_eigval": bool(config["linear_allow_neg_eigval"]),
    }


def param_count(d: Dict[str, Any]) -> int:
    """Every layer: SwiGLU and two output norms. A linear mixer: the fused
    q/k/v projection, the convolution, the two gate vectors a head with
    A_log and dt_bias, the output gate, the output projection and one norm
    weight of d_v. A full mixer: four projections and the q and k norms.
    Embedding, head, final norm."""
    h, H, dv = d["hidden_size"], d["linear_heads"], d["linear_value_dim"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    ffn = 3 * h * d["intermediate_size"] + 2 * h
    linear = (h * d["conv_dim"] + d["conv_kernel"] * d["conv_dim"]
              + 2 * h * H + 2 * H + dv + 2 * h * H * dv)
    full = 2 * h * q + 2 * h * kv + q + kv
    return (d["num_layers"] * ffn + d["linear_layers"] * linear
            + d["full_layers"] * full + 2 * d["vocab_size"] * h + h)


def kv_bytes(d: Dict[str, Any], num_pages: int, page_size: int) -> int:
    """Bytes of the paged KV pool: K and V in bfloat16 for the
    full-attention layers alone. The linear layers' recurrent state
    (``state_bytes``) comes on top; its size follows from ``--max-batch``,
    which this signature is not given."""
    return (2 * d["full_layers"] * num_pages * page_size
            * d["num_kv_heads"] * d["head_dim"] * 2)


def state_bytes_per_slot_layer(d: Dict[str, Any]) -> int:
    """What one slot holds for one linear layer: S [heads, d_k, d_v] in
    float32 and the convolution's last kernel - 1 inputs in bfloat16."""
    s = d["linear_heads"] * d["linear_key_dim"] * d["linear_value_dim"] * 4
    return s + (d["conv_kernel"] - 1) * d["conv_dim"] * 2


def state_bytes(d: Dict[str, Any], max_batch: int) -> int:
    return d["linear_layers"] * max_batch * state_bytes_per_slot_layer(d)
