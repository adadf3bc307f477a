"""The plain reference, and the comparison that decides ``correct``.

A straightforward ``jax.numpy`` forward of the published architecture
(Mistral: RMSNorm, grouped-query attention with rotate-half RoPE, SwiGLU;
Mixtral: the same trunk with a top-k router over E SwiGLU experts whose
top-k scores are renormalised by a softmax), in float32 with
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching. It imports nothing of the program and takes nothing the program
has made: the weights are made here from the seed, by the initialisation
the program documents (normal(0, fan_in^-1/2) per tensor from
``split(key(seed))``, cast to bfloat16, norms at one), and are the same
numbers only because the same seed goes through the same public
``jax.random`` calls.

Run as a child once the window has closed and the server has gone:

    python -m fmabench.reference --job <job.json> --out <result.json>

For each sampled request it runs ONE forward over prompt + served tokens
and reads, at every served position, the gap by which the served token's
logit lies below the reference's best. ``--control int8`` is the control
of "How correct is decided": the same forward with every matmul weight
rounded to per-output-channel int8 (the nearest precision below the
bfloat16 the configuration states) is put in the program's place, and the
gap read is that of the token IT puts first.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are padded to a multiple of SEQ_BUCKET and the served
#: positions to a multiple of ROW_BUCKET, so that few shapes ever compile
#: (each is cached on disk after its first run)
SEQ_BUCKET = 512
ROW_BUCKET = 512
MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def init_weights(seed: int, d: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded bf16 weights, layer-stacked. See the module docstring."""
    h, L, f = d["hidden_size"], d["num_layers"], d["intermediate_size"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    E = d["num_experts"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(jnp.bfloat16)

    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    if E > 1:
        ks = jax.random.split(k_layers, 8)
        ffn = {
            "router": dense(ks[4], (L, h, E), h),
            "w_gate": dense(ks[5], (L, E, h, f), h),
            "w_up": dense(ks[6], (L, E, h, f), h),
            "w_down": dense(ks[7], (L, E, f, h), f),
        }
    else:
        ks = jax.random.split(k_layers, 7)
        ffn = {
            "w_gate": dense(ks[4], (L, h, f), h),
            "w_up": dense(ks[5], (L, h, f), h),
            "w_down": dense(ks[6], (L, f, h), f),
        }
    layers = {
        "attn_norm": ones((L, h)),
        "wq": dense(ks[0], (L, h, q), h),
        "wk": dense(ks[1], (L, h, kv), h),
        "wv": dense(ks[2], (L, h, kv), h),
        "wo": dense(ks[3], (L, q, h), q),
        "mlp_norm": ones((L, h)),
        **ffn,
    }
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": layers,
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def int8_round(w: jnp.ndarray) -> jnp.ndarray:
    """Symmetric per-output-channel int8 and back, in the weight's dtype:
    the scale is the fan-in axis's largest magnitude over 127."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(wf), axis=w.ndim - 2, keepdims=True) / 127.0, 1e-8
    )
    return (jnp.clip(jnp.round(wf / scale), -127, 127) * scale).astype(w.dtype)


def to_int8(weights: Dict[str, Any]) -> Dict[str, Any]:
    layers = dict(weights["layers"])
    for name in MATMUL_WEIGHTS:
        layers[name] = int8_round(layers[name])
    return {**weights, "layers": layers, "lm_head": int8_round(weights["lm_head"])}


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """Rotate-half RoPE. x: [s, heads, hd]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(x, gate, up, down):
    f32 = jnp.float32
    return (jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))) @ down.astype(f32)


def _ffn(d, lp, x):
    if d["num_experts"] <= 1:
        return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    logits = x @ lp["router"].astype(jnp.float32)  # [s, E]
    top_vals, top_idx = jax.lax.top_k(logits, d["experts_per_token"])
    probs = jax.nn.softmax(top_vals, axis=-1)
    weights = jnp.sum(
        jax.nn.one_hot(top_idx, d["num_experts"], dtype=jnp.float32)
        * probs[..., None], axis=-2,
    )  # [s, E], zero off the top k

    def one_expert(acc, ew):
        gate, up, down, w = ew
        return acc + _swiglu(x, gate, up, down) * w[:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T),
    )
    return out


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    S = ids.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    pos = jnp.arange(S)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    x = weights["embed"][ids].astype(f32)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], d["rms_eps"])
        q = (h @ lp["wq"].astype(f32)).reshape(S, heads, hd)
        k = (h @ lp["wk"].astype(f32)).reshape(S, kvh, hd)
        v = (h @ lp["wv"].astype(f32)).reshape(S, kvh, hd)
        q, k = _rope(q, pos, d["rope_theta"]), _rope(k, pos, d["rope_theta"])
        g = heads // kvh
        qg = q.reshape(S, kvh, g, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(S, heads * hd) @ lp["wo"].astype(f32)
        h = _rms(x, lp["mlp_norm"], d["rms_eps"])
        return x + _ffn(d, lp, h), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rms(x[rows], weights["final_norm"], d["rms_eps"])
    return x @ weights["lm_head"].astype(f32)


def make_gap_fn(d: Dict[str, Any], control: bool):
    """jit: (weights, [control weights,] ids, length, rows, served) -> gaps.

    Program check: the gap of the SERVED token under the reference.
    Control: the gap, under the reference, of the token the low-precision
    forward puts first at the same position."""

    def gaps(weights, low, ids, length, rows, served):
        with jax.default_matmul_precision("highest"):
            ref = forward_logits(d, weights, ids, length, rows)
            if control:
                served = jnp.argmax(
                    forward_logits(d, low, ids, length, rows), axis=-1
                )
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]

    return jax.jit(gaps)


def _pad(xs: List[int], n: int) -> np.ndarray:
    out = np.zeros((n,), dtype=np.int32)
    out[: len(xs)] = xs
    return out


#: a gap this wide is a token the reference ranks clearly lower (in the MoE
#: model a router near-tie that fell the other way), not the rounding of a
#: near-tie between two logits (PERF.md section 2)
BIG_GAP = 0.1


def reduce_gaps(per_request_gaps: List[np.ndarray]) -> Dict[str, Any]:
    """The numbers a configuration may have compared (``harness.GAP_NUMBERS``)
    from the gaps of every served token, and each request's own readings."""
    arr = np.concatenate(per_request_gaps).astype(np.float64)
    return {
        "compared_tokens": int(arr.size),
        "finite": bool(np.isfinite(arr).all()),
        "gap_max": float(arr.max()),
        "gap_mean": float(arr.mean()),
        "gap_p95": float(np.percentile(arr, 95)),
        "nonzero_share": float((arr > 0).mean()),
        "big_share": float((arr > BIG_GAP).mean()),
        "quantiles": {
            f"p{q}": float(np.percentile(arr, q)) for q in (50, 90, 99)
        },
        "per_request": [
            {"tokens": int(g.size), "gap_max": float(g.max()),
             "gap_mean": float(g.mean()), "gap_p95": float(np.percentile(g, 95)),
             "big": int((g > BIG_GAP).sum()), "nonzero": int((g > 0).sum())}
            for g in per_request_gaps
        ],
    }


def compare(
    d: Dict[str, Any], seed: int, requests: List[Dict[str, Any]], control: str = ""
) -> Dict[str, Any]:
    """Gaps for every served token of every request, reduced; the gaps
    themselves go along under ``gaps`` (limits are set from them)."""
    # the seed is traced (one compile serves every seed); jax.random.key
    # keeps the low 32 bits of a larger Python int, and so does this
    weights = jax.jit(functools.partial(init_weights, d=d))(
        np.uint32(int(seed) % 2**32)
    )
    low = jax.jit(to_int8)(weights) if control == "int8" else None
    if control not in ("", "int8"):
        raise ValueError(f"unknown control {control!r}")
    gap_fn = make_gap_fn(d, bool(control))
    gaps: List[np.ndarray] = []
    for req in requests:
        prompt, tokens = req["prompt"], req["tokens"]
        n = len(tokens)
        ids = prompt + tokens[:-1]
        S = -(-len(ids) // SEQ_BUCKET) * SEQ_BUCKET
        R = -(-n // ROW_BUCKET) * ROW_BUCKET
        rows = _pad(list(range(len(prompt) - 1, len(ids))), R)
        gaps.append(np.asarray(gap_fn(
            weights, low, _pad(ids, S), np.int32(len(ids)), rows,
            _pad(tokens, R),
        ))[:n])
    out = reduce_gaps(gaps)
    for row, req in zip(out["per_request"], requests):
        row["prompt_len"] = len(req["prompt"])
    out["gaps"] = [[float(x) for x in g] for g in gaps]
    return out


def main(argv: List[str]) -> int:
    p = argparse.ArgumentParser(prog="fmabench.reference")
    p.add_argument("--job", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--control", default="")
    args = p.parse_args(argv)
    with open(args.job, "r", encoding="utf-8") as f:
        job = json.load(f)
    platform = jax.devices()[0].platform
    if platform != job["platform"]:
        print(f"reference needs a {job['platform']} device, JAX has {platform}",
              file=sys.stderr)
        return 3
    t0 = time.monotonic()
    out = compare(job["dims"], int(job["seed"]), job["requests"], args.control)
    out["seconds"] = time.monotonic() - t0
    out["platform"] = platform
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
