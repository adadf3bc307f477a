"""The comparison that decides ``correct``: what every family shares.

The plain reference itself is a file of the configuration's family
(``families/<family>/reference.py``: ``init_weights``, ``forward_logits``,
``MATMUL_WEIGHTS``), found by the name the job carries: a straightforward
``jax.numpy`` forward of the published architecture, in float32 with
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching. It imports nothing of the program and takes nothing the program
has made: the weights are made from the seed, by the family's own file.
Here are the job, the buckets, the gap function, the control, the
reduction of the gaps and ``main``.

Run as a child once the window has closed and the server has gone:

    python -m fmabench.reference --job <job.json> --out <result.json>

For each sampled request it runs ONE forward over prompt + served tokens
and reads, at every served position, the gap by which the served token's
logit lies below the reference's best. ``--control int8`` is the control
of "How correct is decided": the same forward with every matmul weight the
family lists rounded to per-output-channel int8 (the nearest precision
below the bfloat16 the configurations state) is put in the program's
place, and the gap read is that of the token IT puts first.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from types import ModuleType
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import spec

#: sequences are padded to a multiple of SEQ_BUCKET and the served
#: positions to a multiple of ROW_BUCKET, so that few shapes ever compile
#: (each is cached on disk after its first run)
SEQ_BUCKET = 512
ROW_BUCKET = 512


def int8_round(w: jnp.ndarray) -> jnp.ndarray:
    """Symmetric per-output-channel int8 and back, in the weight's dtype:
    the scale is the fan-in axis's largest magnitude over 127."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(wf), axis=w.ndim - 2, keepdims=True) / 127.0, 1e-8
    )
    return (jnp.clip(jnp.round(wf / scale), -127, 127) * scale).astype(w.dtype)


def to_int8(weights: Dict[str, Any], paths: List[str]) -> Dict[str, Any]:
    """``weights`` with the leaves at the dotted ``paths`` rounded."""

    def rounded(tree: Any, keys: List[str]) -> Any:
        if not keys:
            return int8_round(tree)
        return {**tree, keys[0]: rounded(tree[keys[0]], keys[1:])}

    for path in paths:
        weights = rounded(weights, path.split("."))
    return weights


def make_gap_fn(family: ModuleType, d: Dict[str, Any], control: bool):
    """jit: (weights, [control weights,] ids, length, rows, served) -> gaps.

    Program check: the gap of the SERVED token under the reference.
    Control: the gap, under the reference, of the token the low-precision
    forward puts first at the same position."""

    def gaps(weights, low, ids, length, rows, served):
        with jax.default_matmul_precision("highest"):
            ref = family.forward_logits(d, weights, ids, length, rows)
            if control:
                served = jnp.argmax(
                    family.forward_logits(d, low, ids, length, rows), axis=-1
                )
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]

    return jax.jit(gaps)


def _pad(xs: List[int], n: int) -> np.ndarray:
    out = np.zeros((n,), dtype=np.int32)
    out[: len(xs)] = xs
    return out


#: a gap this wide is a token the reference ranks clearly lower (with routed
#: experts, a router near-tie that fell the other way), not the rounding of
#: a near-tie between two logits (PERF.md section 2)
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
    family: ModuleType, d: Dict[str, Any], seed: int,
    requests: List[Dict[str, Any]], control: str = "",
) -> Dict[str, Any]:
    """Gaps for every served token of every request, reduced; the gaps
    themselves go along under ``gaps`` (limits are set from them).
    ``family`` is the family's loaded ``reference.py``."""
    if control not in ("", "int8"):
        raise ValueError(f"unknown control {control!r}")
    # the seed is traced (one compile serves every seed); jax.random.key
    # keeps the low 32 bits of a larger Python int, and so does this
    weights = jax.jit(functools.partial(family.init_weights, d=d))(
        np.uint32(int(seed) % 2**32)
    )
    low = None
    if control:
        low = jax.jit(
            functools.partial(to_int8, paths=list(family.MATMUL_WEIGHTS))
        )(weights)
    gap_fn = make_gap_fn(family, d, bool(control))
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
    family = spec.Family(job["family"], job["data_dir"]).part("reference")
    out = compare(
        family, job["dims"], int(job["seed"]), job["requests"], args.control
    )
    out["seconds"] = time.monotonic() - t0
    out["platform"] = platform
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
