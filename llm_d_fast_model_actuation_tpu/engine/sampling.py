"""Token sampling, in-jit (no host round-trip per step).

Greedy when temperature == 0 (selected with `lax.cond`-free arithmetic so
the same compiled fn serves both; temperature is a traced scalar).
Per-request nucleus (top-p) sampling runs over the top-`candidates`
logits — the standard serving approximation (p mass outside the top 64
is negligible for real models) — selected per row by `top_p < 1`, again
branch-free. The sampled token's logprob (full-vocab normalized) is
returned alongside, so the API can serve OpenAI `logprobs` for free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: nucleus sampling truncates to this many candidates before the cumsum
TOP_P_CANDIDATES = 64


@jax.named_scope("sample")
def sample(
    logits: jnp.ndarray,  # [b, vocab] fp32
    key: jax.Array,  # scalar key, or [b] per-row keys (per-request seeds)
    temperature: jnp.ndarray,  # [b] fp32; 0 = greedy
    top_p: "jnp.ndarray | None" = None,  # [b] fp32; >= 1 = full distribution
    top_k: int = 0,  # static; 0 = no truncation
    counts: "jnp.ndarray | None" = None,  # [b, vocab] int32 token counts
    presence_penalty: "jnp.ndarray | None" = None,  # [b] fp32
    frequency_penalty: "jnp.ndarray | None" = None,  # [b] fp32
    alt_k: int = 0,  # static; also return the top-k alternative logprobs
    bias: "jnp.ndarray | None" = None,  # [b, vocab] fp32 logit bias
):
    """Returns (token [b] int32, logprob [b] fp32 of the chosen token) —
    plus, when `alt_k > 0`, (alt_logprobs [b, alt_k] fp32,
    alt_ids [b, alt_k] int32): the top-k of the same raw distribution the
    reported logprob comes from (OpenAI `logprobs`/`top_logprobs`).

    OpenAI-order transform chain: repetition penalties (subtract
    freq*count + pres*[count>0] from the logits) -> temperature ->
    top-p truncation. Penalties shift greedy decoding too. The reported
    logprob is OpenAI-style "raw": normalized over the penalized (and
    top-k-truncated) logits BEFORE temperature scaling and top-p
    truncation — for temperature != 1 or top_p < 1 it is not the exact
    distribution the token was drawn from."""
    if bias is not None:
        # OpenAI logit_bias: added before everything else, so it shifts
        # greedy decoding, the reported logprobs, and the alternatives
        logits = logits + bias
    if counts is not None:
        cf = counts.astype(jnp.float32)
        pen = jnp.zeros_like(logits)
        if frequency_penalty is not None:
            pen = pen + frequency_penalty[:, None] * cf
        if presence_penalty is not None:
            pen = pen + presence_penalty[:, None] * (cf > 0)
        logits = logits - pen
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    norm = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.maximum(temperature, 1e-6)[:, None]
    per_row = getattr(key, "ndim", 0) == 1  # [b] per-request keys
    if per_row:
        key_full, key_nuc = jax.vmap(
            lambda k: tuple(jax.random.split(k))
        )(key)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(key_full, logits / t)
    else:
        key_full, key_nuc = jax.random.split(key)
        sampled = jax.random.categorical(key_full, logits / t, axis=-1)
    if top_p is not None:
        c = min(TOP_P_CANDIDATES, logits.shape[-1])
        vals, idx = jax.lax.top_k(logits, c)  # [b, c] descending
        # nucleus membership over the TEMPERED distribution (OpenAI/vLLM
        # order: temperature first, then top-p truncation)
        probs = jax.nn.softmax(vals / t, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose PRECEDING mass is < p (the first is always kept)
        keep = (csum - probs) < top_p[:, None]
        masked = jnp.where(keep, vals, -jnp.inf)
        if per_row:
            choice = jax.vmap(
                lambda k, row: jax.random.categorical(k, row)
            )(key_nuc, masked / t)
        else:
            choice = jax.random.categorical(key_nuc, masked / t, axis=-1)
        nucleus = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
        sampled = jnp.where(top_p < 1.0, nucleus, sampled)
    tok = jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
    lp = jnp.take_along_axis(norm, tok[:, None].astype(jnp.int32), axis=-1)[:, 0]
    if alt_k > 0:
        alt_lps, alt_ids = jax.lax.top_k(norm, alt_k)
        return tok, lp, alt_lps, alt_ids.astype(jnp.int32)
    return tok, lp
