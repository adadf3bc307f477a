"""The serving engine: continuous batching over a paged KV cache.

A single-process engine instance (one per model, spawned by the launcher)
owning sharded params, the page pool, and two compiled programs:

  * ``_prefill_fn``  — batch-1 prompt ingestion, bucketed to power-of-two
    lengths so at most log2(max_seq) prefill programs are ever compiled;
    samples the first token *inside* the program;
  * ``_chunk_fn(T)`` — T fused decode+sample steps (``lax.scan`` over steps)
    for the whole slot batch, cache donated so page updates are in-place in
    HBM. Exactly two chunk programs ever compile: T = ``decode_chunk``
    (steady state) and T = 1 (drain tail) — compiles are expensive on TPU.

Decode runs every slot every step (static shapes; empty slots write to the
reserved null page and their outputs are ignored) — the XLA-friendly version
of continuous batching: requests join/leave by host-side slot bookkeeping,
the compiled step never changes shape.

The serving path contains NO eager jax ops: scheduler state (last tokens,
positions, per-slot budgets, page table, temperatures, RNG key data) lives in
device arrays threaded through the compiled programs, and the host only
uploads fresh state after an admission/retire edge and downloads the [T, b]
token block once per chunk. This matters twice on TPU: per-op dispatch is
expensive (each eager op is a host round-trip), and eager ops re-specialize
(recompile) when array commitment changes across a sleep/wake cycle — the
reference-framework "wake must not recompile" contract (README.md:16-26)
only holds if the hot path is entirely pre-compiled programs.
"""

from __future__ import annotations

import functools
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence as Seq, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..models import llama, moe
from ..ops.attention import suffix_columns
from ..parallel.mesh import shard_pytree
from ..utils import tracing
from .kv_cache import KVLayout, OutOfPages, PageAllocator, PagePool
from .sampling import sample

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class EngineConfig:
    model: llama.LlamaConfig
    max_batch: int = 8
    page_size: int = 16
    num_pages: int = 2048
    max_seq_len: int = 0  # 0 -> model.max_seq_len
    eos_token_id: int = -1  # -1 = never stop on EOS
    #: additional stopping ids (Llama-3-Instruct declares [eos, eom, eot];
    #: chat turns end with eot, not the primary eos)
    extra_eos_ids: tuple = ()
    #: top-k alternative logprobs computed per emitted token inside the
    #: compiled programs (OpenAI `logprobs`/`top_logprobs`; vLLM caps at
    #: 5). 0 disables the extra top-k + transfer.
    logprobs_topk: int = 5
    #: Attention implementation: "auto" (resolve_attention_impl: pallas on
    #: TPU where the kernels can serve the shape, grouped elsewhere),
    #: "grouped" (GQA-grouped XLA, deferred cache scatter), "pallas"
    #: (hand-written TPU kernels), or "reference" (scatter-first +
    #: repeat-KV XLA — the parity baseline).
    attention_impl: str = "auto"
    #: Max decode steps fused into one compiled program dispatch.
    decode_chunk: int = 8
    #: Automatic prefix caching (engine/prefix_cache.py): page-aligned KV
    #: reuse across requests sharing a prompt prefix. Outputs are
    #: identical with it on or off; on is the serving default (the
    #: reference's engine ships the same as vLLM APC).
    prefix_caching: bool = True
    #: Chunked prefill: prompts longer than this prefill in segments of at
    #: most this many tokens (bounds prefill activation memory and compile
    #: buckets; later segments attend over the paged cache). 0 = off.
    max_prefill_tokens: int = 0
    #: N-gram (prompt-lookup) speculative decoding: propose up to this many
    #: tokens by matching the context's most recent n-gram and verify them
    #: in ONE forward over the paged cache (vLLM's "ngram" speculative
    #: decoding). Every emitted token is the verify forward's own greedy
    #: argmax, so quality equals plain greedy decoding; bitwise equality
    #: with the chunk program is NOT guaranteed at argmax ties (the two
    #: programs reduce bf16 in different orders — the standard spec-decode
    #: caveat). Engages for single-sequence greedy decoding only; 0 = off.
    speculative_ngram: int = 0
    #: Double-buffered decode: dispatch chunk k+1 before reading chunk k's
    #: results, overlapping device compute with the host's fetch+emit —
    #: wins when per-dispatch latency is comparable to chunk compute
    #: (docs/perf.md). Token delivery lags one
    #: chunk. Ignored under gang lockstep. Off by default.
    pipeline_decode: bool = False
    #: Drain-tail policy when the batch's max remaining budget is below
    #: decode_chunk: "single" dispatches T=1 steps (minimal wasted
    #: compute — right when dispatch is cheap), "chunk" runs the full
    #: chunk program once (finished slots freeze in-program, so up to
    #: chunk-1 steps idle but up to chunk-1 dispatch round trips are
    #: saved — right on high-latency links, and the T=1 program never
    #: compiles). "auto" = chunk on TPU, single elsewhere. Outputs are
    #: identical either way (chunk-length invariance).
    drain_tail: str = "auto"
    #: Token-packed mixed-batch serving (docs/perf.md "Mixed-batch
    #: serving"): whenever prefill work is pending, ONE compiled
    #: ``mixed`` program processes a flat [token_budget] buffer packing
    #: prefill segments AND one decode row per running sequence, then
    #: the step falls through to the fused decode chunk — concurrent
    #: prompts neither serialize behind each other nor stall decode,
    #: and the per-bucket prefill/suffix programs are off the packed
    #: path (the warmup plan shrinks to one-or-two token-budget shapes
    #: plus the decode chunks). Off (default) preserves the bucketed
    #: path byte-for-byte. Incompatible with pipeline_decode and
    #: multi-host gangs; requests wanting prompt logprobs (echo) fall
    #: back to the bucketed prefill.
    packed_serving: bool = False
    #: Row capacity of the packed buffer; 0 = auto (max(256, enough for
    #: one decode row-block per slot plus one prefill block), rounded up
    #: to the RAGGED_BLOCK alignment).
    token_budget: int = 0
    #: Self-speculative decoding with the model's OWN multi-token-prediction
    #: module (models/exaone_moe.py), inside the decode chunk and for every
    #: live slot: a step verifies a slot's last token and the module's draft
    #: of the next in one forward over two positions, emits one token or
    #: two, and drafts again. A greedy slot without penalties or bias
    #: accepts a draft that is the main model's own argmax, so its tokens
    #: are those of plain decoding; any other slot never accepts. 1 = on,
    #: for a model that has a module; 0 (default) serves the main path alone.
    speculative_mtp: int = 0

    def __post_init__(self) -> None:
        if self.speculative_mtp not in (0, 1):
            raise ValueError(
                f"speculative_mtp {self.speculative_mtp}: 0 or 1 (this "
                "family's module drafts one token)"
            )
        if not self.speculative_mtp:
            return
        if not getattr(self.model, "nextn_layers", 0):
            raise ValueError(
                f"--speculative-mtp 1: {type(self.model).__name__} has no "
                "multi-token-prediction module to draft with"
            )
        if self.packed_serving:
            raise ValueError(
                "--speculative-mtp is incompatible with --packed-serving "
                "(the mixed program emits one token a slot a step)"
            )
        if not self.model.serve_mtp:
            import dataclasses

            # the module's K and V are a layer of the pages: every size
            # that follows from the model (KVLayout, avals, signatures)
            # sees a model that serves its module
            object.__setattr__(
                self, "model",
                dataclasses.replace(self.model, serve_mtp=True),
            )

    @property
    def seq_len(self) -> int:
        return self.max_seq_len or self.model.max_seq_len

    @property
    def pages_per_seq(self) -> int:
        return -(-self.seq_len // self.page_size)

    @property
    def kv_layout(self) -> KVLayout:
        """The division of the sequence state between pages (of K and V, or
        latent ones), rings and recurrent state (engine/kv_cache.py): from
        the model's window, linear-attention and latent-attention layers,
        the batch and the segment limit — nothing an operator sets."""
        n_window, window = llama.window_layers(self.model)
        segment = prefill_bucket(
            self.max_prefill_tokens or self.seq_len, self.seq_len
        )
        return KVLayout.plan(
            self.model.cache_layers, n_window, window, self.page_size,
            self.seq_len, segment, llama.recurrent_state(self.model),
            llama.latent_cache(self.model),
        )

    @property
    def packed_token_budget(self) -> int:
        """The resolved [token_budget] buffer size: requested (or the
        auto default), rounded up to RAGGED_BLOCK alignment and floored
        so every slot can decode AND at least one prefill block always
        fits — a budget too small to carry the running batch would
        deadlock admission."""
        from ..ops.attention import RAGGED_BLOCK as qb

        want = self.token_budget or 256
        floor = qb * (self.max_batch + 1)
        want = max(want, floor)
        return -(-want // qb) * qb


def resolve_attention_impl(impl: str, model, tp: int = 1) -> str:
    """``auto`` by an explicit rule on what the process can observe: the
    Pallas kernels on a tpu backend when Mosaic can compile them for the
    per-device KV shape (ops/pallas/decode.py:pallas_shape_ok — kv_heads /
    tp * head_dim fills whole 128-lane tiles), the GQA-grouped XLA path
    otherwise. A named impl passes through; an explicit ``pallas`` that
    cannot serve the shape is refused when the engine is built."""
    if impl != "auto":
        return impl
    from ..ops.pallas.decode import pallas_shape_ok

    if jax.default_backend() == "tpu" and pallas_shape_ok(
        *kernel_kv_shape(model, tp)
    ):
        return "pallas"
    return "grouped"


def kernel_kv_shape(model, tp: int = 1) -> Tuple[int, int]:
    """(KV heads, head dim) of a page's row as the paged kernels on one
    device read it: the model's KV heads over ``tp``; a latent page is one
    shared head as wide as its stored row (models/llama.py:latent_cache)."""
    latent = llama.latent_cache(model)
    if latent is not None:
        return 1, latent[1]
    return model.num_kv_heads // tp, model.head_dim


def _host_device():
    """The CPU backend's device, or None (no override: the default device)
    where this process was started without that backend. Looked up at
    every use: a device release tears the backends down."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def prefill_bucket(n: int, seq_len: int) -> int:
    """Power-of-two prefill shape bucket (floor 16) clamped to seq_len —
    the ONE definition shared by live dispatch and the AOT warmup plan
    (exec_pool.warmup_plan). They must agree bit-for-bit: a divergence
    would pool executables at buckets the dispatch never asks for, and
    every lookup would silently miss back to first-touch jit."""
    b = 16
    while b < n:
        b *= 2
    return min(b, seq_len)


def packed_budget_shapes(cfg: EngineConfig) -> List[int]:
    """The one-or-two compiled [token_budget] buffer shapes of a packed
    engine, smallest first: the full budget, preceded by a quarter-size
    buffer (when it usefully differs) so a lightly loaded step — one
    admission, a thin decode batch — neither computes nor pad-counts the
    full budget. The ONE definition shared by live dispatch and the AOT
    warmup plan (exec_pool.warmup_plan), like prefill_bucket above."""
    from ..ops.attention import RAGGED_BLOCK as qb

    full = cfg.packed_token_budget
    small = -(-max(full // 4, qb * (cfg.max_batch + 1)) // qb) * qb
    return [small, full] if small < full else [full]


def mixed_bucket(rows: int, kv_pages: int) -> int:
    """AOT/dispatch bucket id of one compiled mixed-program shape:
    (buffer rows, page-table width). The packed dispatch slices the page
    table to the power-of-two page count the step's longest sequence
    actually needs — BIT-EXACT (the sliced-away entries were hard-masked
    for every row, contributing exact fp32 zeros to the softmax), and it
    bounds the reference twin's O(rows * ctx) gather by live context
    instead of max_seq. Like prefill_bucket, at most log2(pages_per_seq)
    widths ever compile; the warmup plan covers the full width (always
    correct), narrower ones jit on first touch."""
    return (int(rows) << 16) | int(kv_pages)


def kv_pages_bucket(max_kv: int, page_size: int, pages_per_seq: int) -> int:
    """Page-table width covering `max_kv` cache entries, rounded up to
    the {1, 2, 3, 4, 6, 8, 12, ...} bucket ladder (powers of two and
    their 1.5x midpoints — halves the worst-case over-read vs plain
    pow2 at twice the compiled widths, still O(log) shapes), clamped to
    the full table."""
    need = max(1, -(-max_kv // page_size))
    k = 1
    while k < need:
        if k * 3 // 2 >= need and k * 3 % 2 == 0:
            k = k * 3 // 2
            break
        k *= 2
    return min(k, pages_per_seq)


@dataclass
class Request:
    seq_id: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    out_tokens: List[int] = field(default_factory=list)
    out_logprobs: List[float] = field(default_factory=list)
    #: per emitted token: [(token_id, logprob), ...] top-k alternatives of
    #: the raw distribution (filled only when `want_top_logprobs`)
    out_top_logprobs: List[list] = field(default_factory=list)
    #: materialize per-token alternatives on the host (the device always
    #: computes cfg.logprobs_topk; the Python tuple-building per token is
    #: what this gates — most requests never ask for logprobs)
    want_top_logprobs: bool = False
    #: per-request RNG seed (OpenAI/vLLM `seed`): with it, a sampled
    #: (temperature > 0) request's output depends only on (seed, params,
    #: prompt, sampling knobs) — not on batch composition or arrival
    #: order. None = a stream derived from the engine seed and seq_id.
    seed: Optional[int] = None
    #: OpenAI `logit_bias`: token_id -> additive logit bias in [-100,
    #: 100]; applied before temperature/top-p, shifts greedy too. Empty
    #: = off.
    logit_bias: Dict[int, float] = field(default_factory=dict)
    #: vLLM `ignore_eos`: decode the full token budget even when the
    #: model emits eos (benchmark harnesses need length-controlled runs)
    ignore_eos: bool = False
    #: OpenAI `echo` + `logprobs`: logprob of every PROMPT token under the
    #: model (first entry None — nothing precedes it). Requesting this
    #: bypasses the prefix cache: cached pages skip exactly the forward
    #: that would produce these numbers.
    want_prompt_logprobs: bool = False
    prompt_logprobs: List[Optional[float]] = field(default_factory=list)
    #: nucleus sampling threshold; >= 1.0 = full distribution
    top_p: float = 1.0
    #: OpenAI repetition penalties (0 = off); applied to logits before
    #: temperature/top-p over counts of prompt + generated tokens
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    #: stop sequences (token tuples); on match the request finishes and
    #: the matched sequence is stripped from the output (OpenAI semantics)
    stop_seqs: tuple = ()
    pages: List[int] = field(default_factory=list)
    pos: int = 0  # tokens in cache
    slot: int = -1
    #: prompt tokens served from the prefix cache (0 = full prefill)
    cached_tokens: int = 0
    #: how many of `pages` are shared prefix pages (for registration)
    shared_pages: int = 0
    done: bool = False
    #: why the request finished: "length" | "stop" (eos or stop sequence)
    finish_reason: str = ""
    error: Optional[str] = None
    submit_time: float = field(default_factory=time.monotonic)
    #: when the request first won a slot (_admit) — with submit_time this
    #: separates queue wait from prefill inside TTFT
    #: (fma_engine_queue_wait_seconds)
    first_sched_time: Optional[float] = None
    first_token_time: Optional[float] = None
    #: stamped by the serving loop when the finished request leaves the
    #: engine (SLO TPOT judgment + the usage block's decode_tpot_s)
    done_time: Optional[float] = None
    #: Streaming hook: called as on_tokens(req, tokens) on the engine
    #: thread, once for each run of tokens the request is emitted (the
    #: first token, then what every drained decode chunk held for it).
    #: Keep it cheap (enqueue, don't compute). Tokens that could be the
    #: start of a stop sequence are held back until disambiguated, so
    #: streamed output never contains stripped stop-sequence content
    #: (OpenAI semantics).
    on_tokens: Optional[Callable[["Request", List[int]], None]] = None
    #: tokens already delivered to on_tokens (stop-prefix holdback cursor)
    streamed: int = 0
    #: external early-stop request (e.g. a stop STRING matched on decoded
    #: text in the server layer): the engine finishes the request at the
    #: next emitted token instead of decoding to eos/max_tokens
    stop_requested: bool = False
    #: per-token watcher, asked ``stop_watch(token) -> bool`` on the engine
    #: thread about every token as the stop-sequence hold-back releases
    #: it; True sets ``stop_requested``. A request that carries one has
    #: the end of each run decided token by token (engine._run_end).
    stop_watch: Optional[Callable[[int], bool]] = None
    #: packed serving: admitted but the prompt is not fully in cache yet
    #: (req.pos tracks progress); excluded from decode dispatch until the
    #: final prefill segment samples the first token
    prefilling: bool = False
    #: explicit [2] uint32 RNG key data seated instead of the derived
    #: key at admission. Set only on migrated-in requests: a seed-None
    #: request's key is derived from (engine seed, seq_id), both of
    #: which differ on the importing engine, so the exporter pins the
    #: exact key its own admission would have used.
    rng_key_data: Optional[Any] = None
    #: request-lifecycle trace collector (tracing.RequestTrace) or None.
    #: None — the --trace-requests 0 default — keeps every hook on the
    #: serving hot path to a single ``is None`` check.
    trace: Optional[Any] = None
    #: stamped when the trace is finished, so the serving layer's usage
    #: block can surface it after the collector is gone
    trace_id: str = ""
    #: total wall time this request spent preempted (parked + the park /
    #: resume transfers themselves), and the share of it that happened
    #: before the first token — the leg accounting that keeps
    #: queue/prefill/decode legs a partition of submit→done
    preempt_s: float = 0.0
    preempt_pre_token_s: float = 0.0
    #: migrated-in requests: origin trace context ({"trace_id","span_id"})
    #: decoded from the parked bundle, so destination spans join the SAME
    #: trace the source started
    trace_parent: Optional[dict] = None


def validate_logit_bias(lb, vocab_size: int) -> "Dict[int, float] | None":
    """OpenAI logit_bias validation, shared by the HTTP layer (-> 400)
    and add_request (-> per-request error): token ids must be in-vocab,
    values in [-100, 100]. Returns a normalized {int: float} dict."""
    if lb is None:
        return None
    if not isinstance(lb, dict):
        raise ValueError("logit_bias must be an object")
    out: Dict[int, float] = {}
    for k, v in lb.items():
        try:
            t = int(k)
            fv = float(v)
        except (TypeError, ValueError):
            raise ValueError(f"invalid logit_bias entry {k!r}: {v!r}")
        if not (0 <= t < vocab_size):
            raise ValueError(f"logit_bias token {t} outside vocab")
        if not (-100.0 <= fv <= 100.0):
            raise ValueError(f"logit_bias value {fv} outside [-100, 100]")
        out[t] = fv
    return out


def deliver_tokens(req: Request, end: int) -> None:
    """Hand ``req.out_tokens[req.streamed:end]`` to the request's hook in
    ONE call and advance the cursor by what was delivered: a hook that
    raises leaves the whole run to the next delivery."""
    req.on_tokens(req, req.out_tokens[req.streamed:end])
    req.streamed = end


def _stop_holdback(out: List[int], stop_seqs) -> int:
    """Length of the longest suffix of `out` that is a PROPER prefix of
    any stop sequence — tokens that must not be streamed yet because the
    next tokens may complete a stop match (and the whole match is then
    stripped from the output)."""
    best = 0
    for seq in stop_seqs:
        m = min(len(seq) - 1, len(out))
        for k in range(m, best, -1):
            if tuple(out[-k:]) == tuple(seq[:k]):
                best = k
                break
    return best


class EngineAsleep(RuntimeError):
    """The engine's device state is offloaded; wake_up() before serving."""


class SlotStateUnsupported(ValueError):
    """A path that knows pages of K and V alone was asked of a model whose
    sequence state is something else too: the rings of sliding-window
    layers, the recurrent state of linear-attention layers, the latent pages
    of latent-attention layers."""


def slot_state_kinds(model) -> List[str]:
    """What ``model`` keeps of a sequence beside pages of K and V, in
    words: empty for a model whose whole sequence state is such pages."""
    n_window, window = llama.window_layers(model)
    recurrent = llama.recurrent_state(model)
    latent = llama.latent_cache(model)
    kinds = []
    if latent is not None:
        kinds.append(
            f"{model.cache_layers} latent-attention layers whose pages are "
            f"one array of {latent[0]} values a token, not a K and a V"
        )
    if n_window:
        kinds.append(
            f"{n_window} sliding-window layers (window {window}) whose K "
            "and V live in per-sequence rings"
        )
    if recurrent is not None:
        kinds.append(
            f"{recurrent[0]} {getattr(model, 'recurrent_kind', 'linear-attention')} "
            "layers whose recurrent state "
            "and convolution tail live in per-slot arrays"
        )
    return kinds


def refuse_slot_state(model, what: str) -> None:
    """Raise, naming the model, its state and the path, if ``model`` keeps
    per-slot sequence state or latent pages: ``what`` addresses a
    sequence's state as pages of K and V and would read, share or move a
    ring, a slot's recurrent state or a latent page as something else, or
    not at all."""
    kinds = slot_state_kinds(model)
    if kinds:
        raise SlotStateUnsupported(
            f"{type(model).__name__} has {' and '.join(kinds)}: {what} "
            "cannot carry that state yet and refuses this model"
        )


class ProgramSet:
    """The engine's compiled-program surface, built from static config only
    (model config + sampling/eos scalars) — no params, no device state.

    This is what lets the AOT warmup driver (engine/exec_pool.py) construct
    and compile the serving programs for a model that is not resident yet,
    while its weights are still streaming host->device: ``jax.jit`` only
    needs the traced function and abstract avals, so compilation is pure
    host-CPU work that overlaps cleanly with the transfer DMA.

    The engine owns one ProgramSet; the warmup driver builds its own for
    the incoming config and hands the resulting executables over through
    ``InferenceEngine.install_executable`` — jit caches are keyed by
    function identity, so the *executable*, not the jitted wrapper, is the
    unit that crosses between them.
    """

    def __init__(
        self,
        model_cfg,
        logprobs_topk: int,
        eos_token_id: int,
        mesh: Optional[Mesh] = None,
    ) -> None:
        self.model_cfg = model_cfg
        self.alt_k = int(logprobs_topk)
        self.eos = int(eos_token_id)
        #: the engine's mesh: device-RESIDENT scheduler outputs (counts,
        #: bias, last tokens, ...) are pinned replicated on it so their
        #: sharding is a fixed point across dispatches — without the pin
        #: GSPMD shards them however the program liked (e.g. counts over
        #: the tp vocab axis), the next dispatch's input sharding drifts
        #: from the uploaded/compiled one, and every AOT executable
        #: mismatches after its first call
        self.mesh = mesh
        #: the model's multi-token-prediction module drafts inside the
        #: decode chunk (EngineConfig.speculative_mtp): the prompt programs
        #: return a first draft too, the chunk takes and returns one
        self.mtp = bool(getattr(model_cfg, "serve_mtp", False))
        self.prefill = jax.jit(self._make_prefill(False), donate_argnums=(3,))
        self.prefill_plp = jax.jit(self._make_prefill(True), donate_argnums=(3,))
        self.suffix = jax.jit(
            self._make_suffix_prefill(False), donate_argnums=(5,)
        )
        self.suffix_plp = jax.jit(
            self._make_suffix_prefill(True), donate_argnums=(5,)
        )
        self.verify = jax.jit(self._make_verify(), donate_argnums=(4,))
        #: the token-packed mixed-batch programs, one jitted function per
        #: page-table slice width (mixed(kvp), like chunk(T)): jit then
        #: specializes per buffer shape — two budget shapes
        #: (packed_budget_shapes) x O(log) KV widths (kv_pages_bucket)
        #: ever dispatch, and the AOT warmup covers the two full-width
        #: shapes (exec_pool.warmup_plan)
        self._mixed: Dict[int, Any] = {}
        self._chunks: Dict[int, Any] = {}

    def _pin_resident(self, *xs):
        """Constrain device-resident scheduler outputs to the replicated
        sharding the engine uploads them with (no-op off-mesh): state
        that round-trips through dispatches must keep a stable sharding
        or AOT executables mismatch after one call (see __init__)."""
        if self.mesh is None:
            return xs if len(xs) > 1 else xs[0]
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(self.mesh, PartitionSpec())
        pinned = tuple(
            jax.lax.with_sharding_constraint(x, sh) for x in xs
        )
        return pinned if len(pinned) > 1 else pinned[0]

    # -- shared program tails -------------------------------------------------

    def _sample_last(
        self, logits, lens, temp, topp, counts, pres, freq, skey, bias
    ):
        """Shared sampling tail of both prefill programs: take the last
        valid logit, split the request's OWN key, sample — one definition
        so the cache-hit path can never diverge from the cold one."""
        alt_k = self.alt_k
        last = jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1
        )[:, 0]
        key = jax.random.wrap_key_data(skey)
        key, sub = jax.random.split(key)
        out = sample(
            last, sub, temp, top_p=topp,
            counts=counts, presence_penalty=pres, frequency_penalty=freq,
            alt_k=alt_k, bias=bias,
        )
        tok, lp = out[0], out[1]
        alts = out[2:] if alt_k > 0 else (
            jnp.zeros((tok.shape[0], 0), jnp.float32),
            jnp.zeros((tok.shape[0], 0), jnp.int32),
        )
        return tok, lp, alts[0], alts[1], jax.random.key_data(key)

    def _sample_hidden(
        self, params, hidden, lens, temp, topp, counts, pres, freq, skey,
        bias, targets,
    ):
        """``_sample_last`` for a program that has the stream before the
        final norm (``hidden`` [b, s, h]) and no logits yet: the head over
        the last valid row alone, or, for prompt logprobs (``targets``),
        over the whole segment. -> (tok, lp, av, ai, skey, plp)."""
        cfg = self.model_cfg
        if targets is None:
            last = jnp.take_along_axis(
                hidden, (lens - 1)[:, None, None], axis=1
            )
            logits, lens = llama.lm_logits(cfg, params, last), jnp.ones_like(lens)
            plp = jnp.zeros(hidden.shape[:2], jnp.float32)
        else:
            logits = llama.lm_logits(cfg, params, hidden)
            plp = self._prompt_lps(logits, targets)
        return (
            *self._sample_last(
                logits, lens, temp, topp, counts, pres, freq, skey, bias
            ),
            plp,
        )

    @staticmethod
    def _split_slot_keys(skeys, active):
        """Each slot splits its OWN key, and only while ``active``, so a
        request's draw count is a function of its own progress, not of how
        long it shared the batch with others -> (the keys to sample with
        [b], the slots' new key data)."""
        keys = jax.random.wrap_key_data(skeys)  # [b] typed keys
        pairs = jax.vmap(jax.random.split)(keys)  # [b, 2]
        subs = pairs[:, 1]
        new_data = jax.random.key_data(pairs[:, 0])
        return subs, jnp.where(active[:, None], new_data, skeys)

    @staticmethod
    def _prompt_lps(logits, targets):
        """Per-position logprob of `targets` (the NEXT prompt token at
        each position) under the model — OpenAI echo+logprobs."""
        norm = logits - jax.scipy.special.logsumexp(
            logits, axis=-1, keepdims=True
        )
        return jnp.take_along_axis(
            norm, targets[..., None], axis=-1
        )[..., 0]

    # -- program factories ----------------------------------------------------

    def _make_prefill(self, with_plp: bool):
        """Two compiled variants: prompt-logprob scoring is an extra
        vocab-wide logsumexp over the WHOLE bucket — only echo requests
        pay for it. Signatures match, so call sites just pick the
        function."""
        model_cfg = self.model_cfg

        def _prefill(
            params, tokens, seq_lens, cache, page_table, temp, topp,
            counts, pres, freq, skey, bias,
        ):
            logits, cache = llama.prefill(
                params, model_cfg, tokens, seq_lens, cache, page_table,
                mesh=self.mesh,
            )
            tok, lp, av, ai, skey = self._sample_last(
                logits, seq_lens, temp, topp, counts, pres, freq, skey,
                bias,
            )
            if with_plp:
                # position i predicts token i+1: shift the prompt left
                targets = jnp.roll(tokens, -1, axis=1)
                plp = self._prompt_lps(logits, targets)
            else:
                plp = jnp.zeros(tokens.shape, jnp.float32)
            return tok, lp, av, ai, plp, cache, skey

        def _prefill_mtp(
            params, tokens, seq_lens, cache, page_table, temp, topp,
            counts, pres, freq, skey, bias,
        ):
            """The same, and the module over the prompt shifted by one
            (the sampled token after its last position): its pages are
            whole and the first decode step has a draft."""
            hidden, cache = llama.patterned(model_cfg).prefill(
                params, model_cfg, tokens, seq_lens, cache, page_table,
                mesh=self.mesh, hidden=True,
            )
            targets = jnp.roll(tokens, -1, axis=1)
            tok, lp, av, ai, skey, plp = self._sample_hidden(
                params, hidden, seq_lens, temp, topp, counts, pres, freq,
                skey, bias, targets if with_plp else None,
            )
            draft, cache = self._first_draft(
                params, hidden, targets, tok, None, seq_lens, cache,
                page_table, True,
            )
            return tok, lp, av, ai, plp, cache, skey, draft

        return _prefill_mtp if self.mtp else _prefill

    def _first_draft(
        self, params, hidden, targets, tok, start, lens, cache, page_table,
        final,
    ):
        """The prediction module over a prompt segment (``hidden``: the main
        stack's output before the final norm; ``targets``: the token after
        each position): its K and V for the segment, and the draft of the
        token after ``tok``. ``final``: True (a cold prompt: the token after
        the last position is ``tok``, just sampled), or [b] bool per row (a
        later segment of a chunked prompt: where False, the last target is
        the next segment's first token, as given)."""
        b = tok.shape[0]
        last = jnp.where(final, tok, targets[jnp.arange(b), lens - 1])
        dlogits, cache = llama.patterned(self.model_cfg).draft_segment(
            params, self.model_cfg, hidden,
            targets.at[jnp.arange(b), lens - 1].set(last), start, lens,
            cache, page_table, cold=start is None, mesh=self.mesh,
        )
        return jnp.argmax(dlogits, axis=-1).astype(jnp.int32), cache

    def _make_suffix_prefill(self, with_plp: bool):
        model_cfg = self.model_cfg

        def _suffix_prefill(
            params, tokens, targets, start, suffix_lens, cache,
            page_table, temp, topp, counts, pres, freq, skey, bias,
        ):
            logits, cache = llama.prefill_continue(
                params, model_cfg, tokens, start, suffix_lens, cache,
                page_table, mesh=self.mesh,
            )
            tok, lp, av, ai, skey = self._sample_last(
                logits, suffix_lens, temp, topp, counts, pres, freq,
                skey, bias,
            )
            if with_plp:
                # a segment cannot derive its last target (the NEXT
                # segment's first token) from its own tokens, so
                # targets come in
                plp = self._prompt_lps(logits, targets)
            else:
                plp = jnp.zeros(tokens.shape, jnp.float32)
            return tok, lp, av, ai, plp, cache, skey

        def _suffix_prefill_mtp(
            params, tokens, targets, start, suffix_lens, cache,
            page_table, temp, topp, counts, pres, freq, skey, bias,
        ):
            """The same, and the module over the segment. A segment that is
            not its prompt's last brings the next segment's first token as
            its last target; the last one brings -1 there, and the module
            takes the token just sampled."""
            hidden, cache = llama.patterned(model_cfg).prefill_continue(
                params, model_cfg, tokens, start, suffix_lens, cache,
                page_table, hidden=True,
            )
            tok, lp, av, ai, skey, plp = self._sample_hidden(
                params, hidden, suffix_lens, temp, topp, counts, pres, freq,
                skey, bias, jnp.maximum(targets, 0) if with_plp else None,
            )
            final = targets[jnp.arange(tok.shape[0]), suffix_lens - 1] < 0
            draft, cache = self._first_draft(
                params, hidden, targets, tok, start, suffix_lens, cache,
                page_table, final,
            )
            return tok, lp, av, ai, plp, cache, skey, draft

        return _suffix_prefill_mtp if self.mtp else _suffix_prefill

    def _make_verify(self):
        model_cfg = self.model_cfg
        alt_k = self.alt_k

        def _verify(params, tokens, start, window_len, cache, page_table):
            """Speculative verify: run the window [last_token, q1..q_{k-1}]
            through the continue program and return the model's GREEDY next
            token at every window position, with its logprob (the logprobs
            API must not degrade under speculation)."""
            logits, cache = llama.prefill_continue(
                params, model_cfg, tokens, start, window_len, cache,
                page_table, mesh=self.mesh,
            )
            norm = logits - jax.scipy.special.logsumexp(
                logits, axis=-1, keepdims=True
            )
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lps = jnp.take_along_axis(norm, toks[..., None], axis=-1)[..., 0]
            if alt_k > 0:
                avs, ais = jax.lax.top_k(norm, alt_k)
            else:
                b, w = toks.shape
                avs = jnp.zeros((b, w, 0), jnp.float32)
                ais = jnp.zeros((b, w, 0), jnp.int32)
            return toks, lps, avs, ais.astype(jnp.int32), cache

        return _verify

    def _make_mixed(self, kvp: int):
        """The token-packed mixed-batch program: one forward over a flat
        [token_budget] buffer (llama.mixed_step), then the shared
        sampling tail over ONE gathered row per slot — each sequence
        emits at most one token per packed step (a prefill segment's
        first token or a decode step), so the in-program budget/eos
        machinery of the chunk program is unnecessary; the host applies
        it between steps exactly like the bucketed prefill path.

        Scheduler state is DEVICE-RESIDENT, chunk-program style: the
        [b, vocab] token counts and logit-bias mirrors arrive as device
        arrays (donated) and the program maintains them itself —
        ``fresh_on`` slots (admitted this step with no exact-count /
        bias edge) zero their rows, ``count_row`` rows (streamed prompt
        tokens) join their slot's counts BEFORE the sampling tail (so
        the final segment's sample sees the whole prompt, exactly like
        the bucketed prefill's counts row), and each sampling slot's
        emitted token joins the counts after (the chunk program's
        post-sample add). The full-width page table is device-resident
        too; the program slices it to this function's static ``kvp``
        width (bit-exact: the sliced-away entries were hard-masked
        exact zeros). Steady-state per-step H2D is therefore O(rows) —
        the [b, vocab] mirrors re-upload only on dirty edges."""
        model_cfg = self.model_cfg
        alt_k = self.alt_k

        def _mixed(
            params, tokens, row_slot, positions, count_row, sample_rows,
            sample_on, fresh_on, cache, page_table, temps, topps, counts,
            pres, freq, skeys, bias,
        ):
            b = sample_rows.shape[0]
            pt = jax.lax.slice_in_dim(page_table, 0, kvp, axis=1)
            # device-side dirty-edge maintenance: a freshly admitted
            # slot's rows still hold the previous occupant's state —
            # zero them here instead of re-uploading [b, vocab] mirrors
            fresh = fresh_on > 0
            counts = jnp.where(fresh[:, None], 0, counts)
            bias = jnp.where(fresh[:, None], 0.0, bias)
            # streamed prompt rows join their slot's counts BEFORE the
            # sample (penalties see the full prompt at the final
            # segment); padding / decode rows scatter out of bounds
            add_slot = jnp.where(count_row > 0, row_slot, b)
            counts = counts.at[add_slot, tokens].add(1, mode="drop")
            logits, cache = llama.mixed_step(
                params, model_cfg, tokens, row_slot, positions, cache,
                pt, mesh=self.mesh,
            )
            last = logits[sample_rows]  # [b, vocab]
            # per-slot key split, advanced only for slots that sample this
            # step (same discipline as the chunk program's active mask)
            active = sample_on > 0
            subs, skeys = self._split_slot_keys(skeys, active)
            out = sample(
                last, subs, temps, top_p=topps,
                counts=counts, presence_penalty=pres,
                frequency_penalty=freq, alt_k=alt_k, bias=bias,
            )
            tok, lp = out[0], out[1]
            if alt_k > 0:
                av, ai = out[2], out[3]
            else:
                av = jnp.zeros((tok.shape[0], 0), jnp.float32)
                ai = jnp.zeros((tok.shape[0], 0), jnp.int32)
            # the emitted token joins the counts the NEXT step penalizes
            # (host _emit mirrors the same add)
            counts = counts.at[jnp.arange(b), tok].add(
                active.astype(jnp.int32)
            )
            counts, bias = self._pin_resident(counts, bias)
            return tok, lp, av, ai, cache, counts, bias, skeys

        return _mixed

    def _make_chunk(self, T: int):
        model_cfg = self.model_cfg
        eos = self.eos
        alt_k = self.alt_k

        def chunk(
            params, lt, pos, budget, cache, page_table, temps, topps,
            counts, pres, freq, skeys, eos_on, bias,
        ):
            def body(carry, _):
                lt, pos, budget, cache, counts, skeys = carry
                active = budget > 0
                logits, cache = llama.decode_step(
                    params, model_cfg, lt, pos, cache, page_table, active,
                    mesh=self.mesh,
                )
                subs, skeys = self._split_slot_keys(skeys, active)
                out = sample(
                    logits, subs, temps, top_p=topps,
                    counts=counts, presence_penalty=pres,
                    frequency_penalty=freq,
                    alt_k=alt_k, bias=bias,
                )
                nxt, lp = out[0], out[1]
                if alt_k > 0:
                    av, ai = out[2], out[3]
                else:
                    av = jnp.zeros((nxt.shape[0], 0), jnp.float32)
                    ai = jnp.zeros((nxt.shape[0], 0), jnp.int32)
                nxt = jnp.where(active, nxt, lt)
                a32 = active.astype(jnp.int32)
                # the emitted token joins the counts the NEXT step penalizes
                counts = counts.at[jnp.arange(counts.shape[0]), nxt].add(a32)
                pos = pos + a32
                budget = budget - a32
                if eos >= 0:
                    budget = jnp.where(
                        active & (nxt == eos) & (eos_on > 0), 0, budget
                    )
                return (
                    (nxt, pos, budget, cache, counts, skeys),
                    (nxt, lp, av, ai),
                )

            (
                (lt, pos, budget, cache, counts, skeys),
                (toks, lps, avs, ais),
            ) = jax.lax.scan(
                body, (lt, pos, budget, cache, counts, skeys), None, length=T
            )
            lt, pos, budget, counts, skeys = self._pin_resident(
                lt, pos, budget, counts, skeys
            )
            return (
                toks, lps, avs, ais, lt, pos, budget, cache, counts, skeys,
            )

        return chunk

    def _make_chunk_mtp(self, T: int):
        """The decode chunk of a model that drafts with its own prediction
        module (``self.mtp``). A slot carries, beside its last token, a
        draft of the next. One scan step, for every live slot at once:

        1. the main stack over the two positions [last, draft] through the
           decode path (``verify_step``): logits L_0, L_1;
        2. g_1 from L_0 by the usual sampler; the draft is ACCEPTED iff it
           is g_1, the slot is greedy without penalties or bias, g_1 does
           not end it and its budget holds two; then g_2 = argmax L_1. The
           slot emits g_1 or g_1, g_2 and ``pos``, ``budget`` and ``counts``
           move by as many;
        3. the module over the one or two new positions (``draft_step``)
           gives the next draft.

        A rejected draft's K and V are overwritten by the next step's first
        position. Returns per step tokens [b, 2] and how many of them count
        [b], where ``chunk`` returns one token a slot."""
        model_cfg = self.model_cfg
        eos = self.eos
        alt_k = self.alt_k
        family = llama.patterned(model_cfg)

        def chunk(
            params, lt, pos, budget, cache, page_table, temps, topps,
            counts, pres, freq, skeys, eos_on, bias, draft,
        ):
            # a slot that samples, or whose argmax penalties or a bias
            # move, takes L_0 through the sampler and never accepts
            plain = (
                (temps <= 0) & (pres == 0) & (freq == 0)
                & ~jnp.any(bias != 0, axis=-1)
            )
            rows = jnp.arange(counts.shape[0])

            def body(carry, _):
                lt, draft, pos, budget, cache, counts, skeys = carry
                active = budget > 0
                two = active & (budget >= 2)
                logits, hidden, cache = family.verify_step(
                    params, model_cfg, jnp.stack([lt, draft], axis=1), pos,
                    cache, page_table, jnp.stack([active, two], axis=1),
                    mesh=self.mesh,
                )
                subs, skeys = self._split_slot_keys(skeys, active)
                out = sample(
                    logits[:, 0], subs, temps, top_p=topps,
                    counts=counts, presence_penalty=pres,
                    frequency_penalty=freq,
                    alt_k=alt_k, bias=bias,
                )
                g1 = jnp.where(active, out[0], lt)
                ends = (
                    (g1 == eos) & (eos_on > 0) if eos >= 0
                    else jnp.zeros_like(active)
                )
                accept = two & plain & (g1 == draft) & ~ends
                # the second position, greedy: its raw distribution
                norm = logits[:, 1] - jax.scipy.special.logsumexp(
                    logits[:, 1], axis=-1, keepdims=True
                )
                g2 = jnp.argmax(norm, axis=-1).astype(jnp.int32)
                lp2 = jnp.take_along_axis(norm, g2[:, None], axis=-1)[:, 0]
                if alt_k > 0:
                    av2, ai2 = jax.lax.top_k(norm, alt_k)
                    av = jnp.stack([out[2], av2], axis=1)
                    ai = jnp.stack([out[3], ai2.astype(jnp.int32)], axis=1)
                else:
                    av = jnp.zeros((g1.shape[0], 2, 0), jnp.float32)
                    ai = jnp.zeros((g1.shape[0], 2, 0), jnp.int32)
                a32, b32 = active.astype(jnp.int32), accept.astype(jnp.int32)
                # the emitted tokens join the counts the NEXT step penalizes
                counts = counts.at[rows, g1].add(a32).at[rows, g2].add(b32)
                nxt, cache = family.draft_step(
                    params, model_cfg, hidden, jnp.stack([g1, g2], axis=1),
                    pos, cache, page_table,
                    jnp.stack([active, accept], axis=1), b32, mesh=self.mesh,
                )
                draft = jnp.where(
                    active, jnp.argmax(nxt, axis=-1).astype(jnp.int32), draft
                )
                n = a32 + b32
                pos = pos + n
                budget = budget - n
                if eos >= 0:
                    ends = (active & ends) | (accept & (g2 == eos) & (eos_on > 0))
                    budget = jnp.where(ends, 0, budget)
                return (
                    (jnp.where(accept, g2, g1), draft, pos, budget, cache,
                     counts, skeys),
                    (jnp.stack([g1, g2], axis=1),
                     jnp.stack([out[1], lp2], axis=1), av, ai, n),
                )

            (
                (lt, draft, pos, budget, cache, counts, skeys),
                (toks, lps, avs, ais, ns),
            ) = jax.lax.scan(
                body, (lt, draft, pos, budget, cache, counts, skeys), None,
                length=T,
            )
            lt, pos, budget, counts, skeys, draft = self._pin_resident(
                lt, pos, budget, counts, skeys, draft
            )
            return (
                toks, lps, avs, ais, lt, pos, budget, cache, counts, skeys,
                ns, draft,
            )

        return chunk

    def chunk(self, T: int):
        """The jitted T-step decode chunk (cached per T). At most two ever
        compile in serving (T = decode_chunk and T = 1) — compiles are
        expensive on TPU."""
        fn = self._chunks.get(T)
        if fn is None:
            make = self._make_chunk_mtp if self.mtp else self._make_chunk
            # donate scheduler state + cache + counts + key data
            fn = self._chunks[T] = jax.jit(
                make(T), donate_argnums=(1, 2, 3, 4, 8, 11)
            )
        return fn

    def mixed(self, kvp: int):
        """The jitted mixed-batch program at page-table slice width
        `kvp` (cached per width, like chunk(T)): the slice width is a
        closure constant, so the jit specializes per (buffer shape, kvp)
        exactly as the old host-sliced dispatch did — same compile
        count, but the full-width table stays device-resident."""
        fn = self._mixed.get(kvp)
        if fn is None:
            # donate cache + the device-resident counts/bias mirrors
            fn = self._mixed[kvp] = jax.jit(
                self._make_mixed(kvp), donate_argnums=(8, 12, 16)
            )
        return fn


class InferenceEngine:
    def __init__(
        self,
        cfg: EngineConfig,
        params: Optional[Dict[str, Any]] = None,
        mesh: Optional[Mesh] = None,
        seed: int = 0,
    ) -> None:
        m = cfg.model
        tp = mesh.shape["tp"] if mesh is not None else 1
        impl = resolve_attention_impl(cfg.attention_impl, m, tp)
        if impl == "pallas" and jax.default_backend() == "tpu":
            from ..ops.pallas.decode import check_kernel_shape

            check_kernel_shape(*kernel_kv_shape(m, tp))
        if cfg.prefix_caching:
            refuse_slot_state(m, "the prefix cache (--prefix-caching on)")
        if cfg.packed_serving:
            refuse_slot_state(m, "the packed mixed_step path (--packed-serving on)")
        if cfg.speculative_ngram:
            refuse_slot_state(m, "--speculative-ngram")
        self.cfg = cfg
        self._eos_ids = frozenset((cfg.eos_token_id, *cfg.extra_eos_ids))
        self.mesh = mesh
        # thread the attention impl through the model config (per-engine, not
        # a process global — two engines must not clobber each other)
        if m.attention_impl != impl:
            import dataclasses

            m = dataclasses.replace(m, attention_impl=impl)
        # The weights, then the pool, each a stage of the process's start
        # (utils/tracing.py: no-ops once the server listens). A stage ends
        # when its arrays are READY, so that the next does not inherit its
        # device time; a later build (swap, wake) keeps its asynchrony.
        with tracing.stage("start.weights") as st:
            if params is None:
                from ..models.registry import init_params_placed

                params = init_params_placed(jax.random.key(seed), m, mesh)
            elif mesh is not None:
                from ..models.registry import logical_axes_for

                params = shard_pytree(params, mesh, logical_axes_for(m))
            else:
                # Commit to the default device: committed-ness is part of
                # the jit cache key, and the post-wake device_put restore
                # produces committed arrays — starting committed keeps one
                # compiled set.
                params = jax.device_put(params, jax.devices()[0])
            if st.timing:
                jax.block_until_ready(params)
                st.set(bytes=sum(x.nbytes for x in jax.tree.leaves(params)))
        self.params = params
        #: pages for the full-attention layers, rings for the window layers
        self.kv_layout = cfg.kv_layout
        self._model_cfg = m
        with tracing.stage("start.pool") as st:
            self._create_pool()
            if st.timing:
                jax.block_until_ready(self.pool.as_tuple())
                st.set(bytes=self.pool.nbytes())
        self.allocator = PageAllocator(cfg.num_pages)
        if cfg.prefix_caching:
            from .prefix_cache import PrefixCache

            self.prefix_cache: Optional[Any] = PrefixCache(cfg.page_size)
        else:
            self.prefix_cache = None
        b, p = cfg.max_batch, cfg.pages_per_seq
        # Host mirrors of the device scheduler state (source of truth between
        # chunks; re-uploaded only after an admission/retire/prefill edge).
        # A page-table row: the sequence's pages, then the static columns,
        # never rewritten: its slot's ring (window layers), its slot's index
        # (recurrent state).
        self._page_table = np.zeros(
            (b, self.kv_layout.table_width), dtype=np.int32
        )
        self._page_table[:, p:] = self.kv_layout.static_columns(b)
        #: cumulative counters of the two caches and the routed layer,
        #: counted on the host from what the scheduler knows (/v1/stats
        #: "kv" and "moe"): positions that left a ring (overwritten by a
        #: later position of their sequence), tokens through the expert
        #: layers with their (token, expert) assignments, and those of them
        #: whose program ran the layers as grouped matmuls, each token
        #: against its own experts only (models/moe.py:takes_grouped)
        self.window_tokens_evicted = 0
        #: columns of the table rows the suffix program's attention layers
        #: took, a dispatched segment and layer (/v1/stats "kv"): what a
        #: form that scores the whole row would, and what the walk over
        #: column blocks did (ops/attention.py:suffix_columns)
        self.suffix_cols_row = 0
        self.suffix_cols_scored = 0
        self.moe_tokens = 0
        self.moe_routed_tokens = 0
        #: layer applications the dispatched programs ran, counted on the
        #: host once a program (/v1/stats "stack"): a prefill segment, a
        #: packed step or a verify is one forward, a decode chunk T, and a
        #: forward is every layer of every pass, ``loop_steps x num_layers``
        self.layer_passes = 0
        #: the recurrent state's counters (/v1/stats "state"), host-counted
        #: once a program: tokens x linear layers dispatched, and prefill
        #: segments that started from zero or resumed from the slot's state
        self.state_token_updates = 0
        self.state_first_segments = 0
        self.state_resumed_segments = 0
        self._has_experts = getattr(m, "num_experts", 0) > 1
        self._ring_len = self.kv_layout.ring_pages * cfg.page_size
        self._positions = np.zeros((b,), dtype=np.int32)
        self._last_tokens = np.zeros((b,), dtype=np.int32)
        self._temps = np.zeros((b,), dtype=np.float32)
        self._topps = np.ones((b,), dtype=np.float32)
        self._pres = np.zeros((b,), dtype=np.float32)
        self._freqs = np.zeros((b,), dtype=np.float32)
        #: per-slot token counts over prompt + generated (penalties input);
        #: host-exact mirror of the device copy the chunk program maintains
        self._token_counts = np.zeros((b, cfg.model.vocab_size), dtype=np.int32)
        self._budgets = np.zeros((b,), dtype=np.int32)
        #: per-slot draft of the token after the last (speculative_mtp): the
        #: prediction module's, from the prompt programs and then from every
        #: chunk, carried where the last tokens are
        self._drafts = np.zeros((b,), dtype=np.int32)
        #: what the verify steps did (/v1/stats "mtp"), counted from the
        #: drained chunks: steps dispatched, slot-steps that verified a
        #: draft, drafts accepted, tokens the steps emitted
        self.mtp_steps = 0
        self.mtp_drafted = 0
        self.mtp_accepted = 0
        self.mtp_emitted = 0
        #: per-slot eos sensitivity (0 = ignore_eos request): the chunk
        #: program zeroes a slot's budget at eos only when enabled
        self._eos_on = np.ones((b,), dtype=np.int32)
        #: per-slot additive logit bias [b, vocab] (OpenAI logit_bias);
        #: zero rows for requests without one
        self._bias = np.zeros((b, cfg.model.vocab_size), dtype=np.float32)
        self._slots: List[Optional[Request]] = [None] * b
        self._waiting: List[Request] = []
        self._next_seq_id = 1
        #: lifetime emitted-token count (observability; lets tests assert
        #: that early stopping really saved decode work)
        self.total_tokens_emitted = 0
        self._seed = seed
        #: per-slot RNG key data [b, 2]: every slot samples from its OWN
        #: key stream (seeded requests get key(seed); unseeded get a
        #: fold_in of the engine seed and their seq_id), so a seeded
        #: request's draws are independent of batch neighbors. The host
        #: mirror re-syncs from the device after every chunk.
        self._slot_keys = np.zeros((b, 2), dtype=np.uint32)
        self._dev: Optional[Dict[str, Any]] = None  # device scheduler arrays
        self._dirty = True
        #: Multi-host lockstep (engine/multihost.py): the gang leader's
        #: engine broadcasts a control frame before every compiled dispatch
        #: so follower processes replay the identical program. None when
        #: single-host or follower.
        self.lockstep: Optional[Any] = None

        #: the resolved attention implementation (never "auto")
        self.attention_impl = impl

        # One ProgramSet per engine (jit caches key on function identity,
        # so two engines never share a cache); the flat _*_fn attributes
        # keep the historical names the lockstep follower replays through.
        with tracing.stage("start.programs"):
            self.programs = ProgramSet(
                m, cfg.logprobs_topk, cfg.eos_token_id, mesh=mesh
            )
        self._prefill_fn = self.programs.prefill
        self._prefill_plp_fn = self.programs.prefill_plp
        self._suffix_prefill_fn = self.programs.suffix
        self._suffix_prefill_plp_fn = self.programs.suffix_plp
        self._verify_fn = self.programs.verify
        self._jit_programs = {
            "prefill": self.programs.prefill,
            "prefill_plp": self.programs.prefill_plp,
            "suffix": self.programs.suffix,
            "suffix_plp": self.programs.suffix_plp,
        }
        #: executables keyed by (program, shape bucket / chunk T), installed
        #: by the exec-pool warmup driver or adopted from the pool at first
        #: touch (use_exec_pool); dispatch prefers them, a missing entry
        #: with no pool just means first-touch jit compile
        self._aot: Dict[Tuple[str, int], Any] = {}
        #: the pool a first touch asks before it traces (use_exec_pool),
        #: this engine's signature in it, and the (program, bucket) keys
        #: whose executable failed or refused this engine's arguments:
        #: those stay on the lazy jit
        self._exec_pool: Optional[Any] = None
        self._exec_signature = ""
        self._jit_only: set = set()
        #: speculative decoding counters (observability)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._spec_miss_streak = 0
        self._spec_cooldown = 0
        # resolve the drain-tail policy once (mirrors
        # resolve_attention_impl): a typo must fail loudly, not silently
        # behave as "single"
        dt = cfg.drain_tail
        if dt == "auto":
            dt = "chunk" if jax.default_backend() == "tpu" else "single"
        if dt not in ("single", "chunk"):
            raise ValueError(
                f"drain_tail must be auto|single|chunk, got {dt!r}"
            )
        self._drain_tail_chunk = dt == "chunk"
        #: pipelined decode: the dispatched-but-unread chunk, and requests
        #: whose retire awaits that chunk's completion (see _defer_retire)
        self._inflight: Optional[tuple] = None
        self._pending_retire: List[Request] = []
        #: finished outside a step() call (drain_inflight before sleep):
        #: handed back by the next step() so the service resolves futures
        self._orphan_finished: List[Request] = []
        #: zero-drain actuation (engine/parked.py): True while the KV
        #: pool's device arrays were dropped by park_requests — the
        #: sleeper's state then excludes the pool, and set_state rebuilds
        #: a fresh one (rebuild_kv_pool) on restore
        self.kv_detached = False
        #: set by the service when --zero-drain applies to this engine:
        #: pricing peeks (plan_swap, _offload_wire_bytes) then size the
        #: offload WITHOUT the KV pool — matching what the actual
        #: park-then-offload will move
        self.zero_drain_park = False
        # -- token-packed mixed-batch serving (cfg.packed_serving) ----------
        self._packed = bool(cfg.packed_serving)
        if self._packed and cfg.pipeline_decode:
            # a packed step would race the in-flight chunk for the same
            # slots; the packed path already hides prefill behind decode
            raise ValueError(
                "packed_serving is incompatible with pipeline_decode"
            )
        self._token_budget = cfg.packed_token_budget if self._packed else 0
        #: packing alignment: the Pallas ragged kernel requires each
        #: sequence's run of rows to start on a RAGGED_BLOCK boundary
        #: (a kernel block holds one sequence) — on meshes too, where
        #: each shard_map shard replays the same block metadata over
        #: its head slice (ops/pallas/ragged.py). The XLA twin computes
        #: every row independently, so engines resolved to a non-pallas
        #: impl pack DENSELY: same outputs bit-for-bit, fewer padded
        #: rows
        from ..ops.attention import RAGGED_BLOCK

        self._pack_align = RAGGED_BLOCK if impl == "pallas" else 1
        # every buffer shape the packed dispatch can pick is whole kernel
        # blocks, so the ragged kernel's divisibility error is unreachable
        assert not self._packed or all(
            n % RAGGED_BLOCK == 0 for n in packed_budget_shapes(cfg)
        )
        #: packed engines track a second, cheaper staleness tier: the
        #: small per-slot mirrors (last tokens, positions, budgets, page
        #: table, temps/top-p/penalties, keys, eos) changed host-side but
        #: the [b, vocab] counts/bias device state is still exact — the
        #: next dispatch refreshes ONLY the small arrays
        #: (_upload_sched_rows, O(b·pages_per_seq) bytes) instead of the
        #: O(b·vocab) full re-upload. Bucketed engines never set it.
        self._rows_stale = False
        #: slots admitted by the packed path whose device counts/bias
        #: rows still hold the previous occupant's state: the next mixed
        #: dispatch zeroes them in-program (fresh_on); a full mirror
        #: upload makes the zeroing moot and clears the set
        self._fresh_slots: set = set()
        #: cumulative host->device scheduler/dispatch bytes per serving
        #: path (fma_engine_step_h2d_bytes_total): "packed" counts
        #: mixed-program inputs plus every scheduler upload of a packed
        #: engine, "bucketed" counts the bucketed prefill/suffix/spec
        #: dispatch inputs and a bucketed engine's scheduler uploads
        self.step_h2d_bytes: Dict[str, int] = {"packed": 0, "bucketed": 0}
        #: bytes per padded activation row (pad-waste accounting):
        #: one embedding row of the model dtype
        self._pad_token_bytes = m.hidden_size * jnp.dtype(m.dtype).itemsize
        #: cumulative activation-padding waste per dispatch path, in
        #: bytes (fma_engine_prefill_pad_waste_bytes_total): "bucketed"
        #: counts power-of-two prefill bucket padding, "packed" counts
        #: every computed-but-invalid row of the mixed buffer
        self.pad_waste_bytes: Dict[str, int] = {"packed": 0, "bucketed": 0}
        #: valid-token accounting mirrors for the same two paths (the
        #: denominators of a pad-waste fraction)
        self.dispatch_tokens: Dict[str, int] = {"packed": 0, "bucketed": 0}
        #: packed-step lifetime counters (observability)
        self.packed_steps = 0
        self.packed_tokens_total = 0
        #: per-step stats of the most recent step() (None when the step
        #: did not dispatch the packed program) — the service mirrors
        #: these into the packed histogram/occupancy metrics and span
        self.last_step_stats: Optional[Dict[str, Any]] = None

    def _create_pool(self) -> None:
        """A fresh device sequence state (pages of K and V, or latent ones;
        rings where the model has window layers; zeroed recurrent state
        where it has linear-attention layers), committed to the engine's
        placement."""
        m, cfg, lay = self._model_cfg, self.cfg, self.kv_layout
        self.pool = PagePool.create(
            lay.global_layers,
            cfg.num_pages,
            cfg.page_size,
            m.num_kv_heads,
            m.head_dim,
            dtype=m.dtype,
            mesh=self.mesh,
            ring_shape=lay.ring_shape(
                cfg.max_batch, cfg.page_size, m.num_kv_heads, m.head_dim
            ),
            state_shapes=lay.state_shapes(cfg.max_batch),
            latent_width=lay.latent_width,
        )
        if self.mesh is None:
            self.pool.replace(
                jax.device_put(self.pool.as_tuple(), jax.devices()[0])
            )

    def _count_forward(self, first: int, tokens: int, rows: int) -> None:
        """Host counters of the forwards that took one sequence from
        position ``first`` through ``tokens`` more, in a program traced
        with ``rows`` rows (a prefill bucket, the decode batch): tokens
        through the expert layers, routed or not by the rule the trace
        went by, and positions overwritten in the sequence's ring."""
        if self._has_experts:
            self.moe_tokens += tokens
            if moe.takes_grouped(
                self._model_cfg, rows,
                moe.stored_expert_stack(self._model_cfg, self.params),
                self.mesh,
            ):
                self.moe_routed_tokens += tokens
        if self._ring_len:
            self.window_tokens_evicted += max(
                0, first + tokens - self._ring_len
            ) - max(0, first - self._ring_len)
        self.state_token_updates += tokens * self.kv_layout.state_layers

    def _count_segment(self, start_pos: int, suffix_rows: int = 0) -> None:
        """A prefill segment at ``start_pos`` was dispatched: with recurrent
        state it starts from zero or resumes from its slot's, and through
        the suffix program (``suffix_rows``: its bucket) every layer with
        pages attends over its table row, plain or a ring (latent pages: the
        whole row, models/kimi_linear.py:mla_plain_attention)."""
        lay = self.kv_layout
        if lay.state_layers:
            if start_pos:
                self.state_resumed_segments += 1
            else:
                self.state_first_segments += 1
        if suffix_rows:
            ps = self.cfg.page_size
            for layers, width, ring in (
                (lay.global_layers, lay.pages_per_seq * ps, False),
                (lay.window_layers, self._ring_len, True),
            ):
                self.suffix_cols_row += layers * width
                self.suffix_cols_scored += layers * (
                    width if lay.latent_width else suffix_columns(
                        start_pos, suffix_rows, width, ps, ring=ring
                    )
                )

    def _count_passes(self, forwards: int) -> None:
        """``forwards`` whole forwards were dispatched (one a prefill
        segment, T a decode chunk), each every layer of every pass."""
        m = self._model_cfg
        self.layer_passes += forwards * m.loop_steps * m.num_layers

    def cache_stats(self) -> Dict[str, Dict[str, int]]:
        """The ``stack``, ``kv``, ``state`` and ``moe`` blocks of
        ``/v1/stats``."""
        m, lay = self._model_cfg, self.kv_layout
        state_bytes = lay.state_nbytes(
            self.cfg.max_batch, jnp.dtype(m.dtype).itemsize
        )
        experts = getattr(m, "num_experts", 0)
        expert_layers = getattr(m, "expert_layers", m.num_layers)
        per_token = expert_layers * getattr(m, "experts_per_token", 0)
        itemsize = jnp.dtype(m.dtype).itemsize
        latent_layers = m.cache_layers if lay.latent_width else 0
        return {
            "stack": {
                "num_layers": m.num_layers,
                "loop_steps": m.loop_steps,
                "cache_layers": m.cache_layers,
                "layer_passes": self.layer_passes,
                # only for a family whose layers are ONE sub-layer each
                # (models/nemotron_h.py): how many there are of each kind
                **(
                    {"layer_kinds": dict(m.layer_kind_counts)}
                    if hasattr(m, "layer_kind_counts") else {}
                ),
            },
            "kv": {
                "bytes_per_token": PagePool.page_nbytes(
                    m.cache_layers, 1, m.num_kv_heads, m.head_dim,
                    dtype=m.dtype, latent_width=lay.latent_width,
                ),
                # latent pages: what the algorithm reads of a token (as
                # counted) and what its rows take as stored (as laid out,
                # ``bytes_per_token``), all latent layers together
                "latent_layers": latent_layers,
                "latent_bytes_per_token": (
                    latent_layers * lay.latent_counted * itemsize
                ),
                "latent_bytes_per_token_laid_out": (
                    latent_layers * lay.latent_width * itemsize
                ),
                "global_layers": lay.global_layers,
                "window_layers": lay.window_layers,
                "window": lay.window,
                "ring_tokens": self._ring_len,
                "global_pages_in_use": (
                    self.cfg.num_pages - 1 - self.allocator.available
                ),
                "ring_bytes": self.pool.ring_nbytes(),
                "window_tokens_evicted": self.window_tokens_evicted,
                "suffix_cols_row": self.suffix_cols_row,
                "suffix_cols_scored": self.suffix_cols_scored,
                "suffix_cols_skipped": (
                    self.suffix_cols_row - self.suffix_cols_scored
                ),
            },
            "state": {
                "layers": lay.state_layers,
                "bytes_per_slot": state_bytes // self.cfg.max_batch,
                "bytes": state_bytes,
                "token_updates": self.state_token_updates,
                "first_segments": self.state_first_segments,
                "resumed_segments": self.state_resumed_segments,
            },
            "moe": {
                "experts": experts if experts > 1 else 0,
                # a config that holds a share of its router's experts
                # (models/moe.py): how many are here, of how many routed over
                "experts_held": experts if experts > 1 else 0,
                "router_width": (
                    getattr(m, "router_outputs", 0) if experts > 1 else 0
                ),
                # only for a family whose routed experts work in a space
                # narrower than the residual stream: its width
                **(
                    {"latent_size": m.latent_size}
                    if hasattr(m, "latent_size") else {}
                ),
                "tokens": self.moe_tokens,
                "routed_tokens": self.moe_routed_tokens,
                "assignments": self.moe_tokens * per_token,
            },
            # self-speculative decoding (EngineConfig.speculative_mtp):
            # decode steps dispatched, slot-steps that verified a draft,
            # drafts accepted, tokens those steps emitted; zeros when off
            "mtp": {
                "on": int(self.programs.mtp),
                "steps": self.mtp_steps,
                "drafted": self.mtp_drafted,
                "accepted": self.mtp_accepted,
                "emitted": self.mtp_emitted,
            },
        }

    # -- compiled-program dispatch (AOT executables > lazy jit) --------------

    def install_executable(self, program: str, bucket: int, compiled: Any) -> None:
        """Adopt an AOT-compiled executable for (program, shape bucket /
        chunk T) — the exec-pool warmup's delivery point (engine/
        exec_pool.py). Dispatch prefers installed executables; a missing
        entry just means first-touch jit compile, exactly as before."""
        self._aot[(program, int(bucket))] = compiled

    def clear_executables(self) -> None:
        """Forget installed AOT executables. Device release destroys the
        PJRT client that owns them; the service re-validates pool entries
        (or recompiles lazily) on wake."""
        self._aot.clear()

    def use_exec_pool(self, pool: Any) -> None:
        """From here on a serving program's first touch asks `pool` before
        anything is traced (``_adopt_program``). Only a pool whose entries
        outlive the process counts (``ExecutablePool.persistent``: on, and
        this backend's serialized executables trusted — the TPU); any
        other leaves the engine on the lazy jit, exactly as without one."""
        from .exec_pool import exec_signature, mesh_shape

        if pool is None or not pool.persistent:
            self._exec_pool = None
            return
        self._exec_pool = pool
        self._exec_signature = exec_signature(self.cfg, mesh_shape(self.mesh))

    def _jit_program(self, program: str, bucket: int):
        if program == "chunk":
            return self.programs.chunk(bucket)
        if program == "mixed":
            # bucket = mixed_bucket(rows, kvp): the page-table slice
            # width picks the jitted specialization (engine.mixed_bucket)
            return self.programs.mixed(bucket & 0xFFFF)
        return self._jit_programs[program]

    def _pool_key(self, program: str, bucket: int) -> str:
        from .exec_pool import exec_key

        return exec_key(self._exec_signature, program, bucket)

    def _adoptable(self, key: Tuple[str, int]) -> bool:
        return self._exec_pool is not None and key not in self._jit_only

    def _adopt_program(self, program: str, bucket: int, args) -> Optional[Any]:
        """First touch of (program, bucket) with a pool attached: the
        pool's executable, from memory or reloaded from its spill
        directory with nothing traced; on a miss the program is lowered
        from this call's own arguments (so its avals are the live ones by
        construction), compiled once through the persistent cache, and
        spilled for the next start. Installed either way. Any failure
        leaves the key to the lazy jit for this engine's life."""
        from ..utils import compile_cache

        pool, fn = self._exec_pool, self._jit_program(program, bucket)
        key = self._pool_key(program, bucket)
        try:
            t0 = time.time()  # jax's compile events are on this clock too
            comp = pool.get(key)
            if comp is not None:
                compile_cache.count_reload(fn.__name__, t0, time.time())
            else:
                comp = fn.lower(*args).compile()
                pool.put(key, comp, compile_s=time.time() - t0)
        except Exception:  # noqa: BLE001 — the lazy jit is always there
            logger.warning(
                "no executable for %s@%s: serving it through jit",
                program, bucket, exc_info=True,
            )
            self._jit_only.add((program, bucket))
            return None
        self._aot[(program, bucket)] = comp
        return comp

    def _call_program(self, program: str, bucket: int, *args):
        """Dispatch one compiled program: the executable installed for
        this (program, bucket) — by the warmup, or adopted from the pool
        at this first touch — else the lazily-jitted default. An
        aval/sharding mismatch from the executable (e.g. a level-2 wake
        rebuilt params uncommitted) raises TypeError BEFORE execution
        starts, so the donated cache is untouched — drop the stale entry,
        in the pool too, and re-dispatch through jit from then on."""
        key = (program, bucket)
        comp = self._aot.get(key)
        if comp is None and self._adoptable(key):
            comp = self._adopt_program(program, bucket, args)
        if comp is not None:
            try:
                return comp(*args)
            except (TypeError, ValueError):
                # both are pre-execution argument checks (aval mismatch
                # = TypeError, input-sharding mismatch = ValueError), so
                # the donated state is untouched — drop the stale entry
                # and re-dispatch through jit
                self._aot.pop(key, None)
                if self._exec_pool is not None:
                    logger.warning(
                        "the executable for %s@%s refused its arguments: "
                        "serving it through jit", program, bucket,
                        exc_info=True,
                    )
                    self._jit_only.add(key)
                    self._exec_pool.discard(self._pool_key(program, bucket))
        return self._jit_program(program, bucket)(*args)

    def _chunk_fn(self, T: int):
        """The T-step decode dispatch target. Gang followers replay this
        name directly (engine/multihost.py) — they never carry AOT
        entries nor a pool (warmup and the service skip gangs), so they
        get the bare jit program; a single-host engine with an installed
        chunk executable, or a pool to adopt one from, routes through
        _call_program's AOT-prefer/TypeError-drop dispatch."""
        key = ("chunk", T)
        if key not in self._aot and not self._adoptable(key):
            return self.programs.chunk(T)
        return functools.partial(self._call_program, "chunk", T)

    # -- device scheduler state ---------------------------------------------

    def _h2d_path(self) -> str:
        """step_h2d_bytes attribution for scheduler uploads: the engine's
        serving path (a packed engine's chunk re-uploads are packed-path
        cost; bucketed engines only ever have the bucketed path)."""
        return "packed" if self._packed else "bucketed"

    def _sched_sharding(self):
        """Placement of the device scheduler arrays: plain default-device
        on single-device engines (committed-ness stays out of the jit
        key exactly as before); explicitly REPLICATED on a mesh, so the
        live arrays carry the same sharding the AOT warmup lowers
        against (exec_pool.abstract_args) — an uncommitted array and a
        NamedSharding aval would never match at Compiled-call time.
        Multi-host gang meshes keep the legacy uncommitted placement: a
        host-numpy device_put onto a cross-process sharding is
        jax-version-sensitive, and gangs never carry AOT executables
        (warmup skips followers; the in-program _pin_resident still
        stabilizes their resident state from the second dispatch on)."""
        if self.mesh is None:
            return None
        pidx = jax.process_index()
        if any(d.process_index != pidx for d in self.mesh.devices.flat):
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    #: the [b, vocab] scheduler mirrors the packed path's programs
    #: maintain DEVICE-side between dirty edges — excluded from the
    #: small-tier refresh (_upload_sched_rows)
    _VOCAB_MIRRORS = ("counts", "bias")

    def _sched_mirrors(self) -> Dict[str, np.ndarray]:
        """The ONE canonical name -> host-mirror mapping both upload
        tiers derive from: a mirror added here reaches the full upload
        AND the packed path's small-tier refresh (a hand-maintained
        second dict would silently serve stale device state on packed
        engines only)."""
        mirrors = {
            "lt": self._last_tokens,
            "pos": self._positions,
            "budget": self._budgets,
            "pt": self._page_table,
            "temps": self._temps,
            "topp": self._topps,
            "counts": self._token_counts,
            "pres": self._pres,
            "freq": self._freqs,
            "skeys": self._slot_keys,
            "eos_on": self._eos_on,
            "bias": self._bias,
        }
        if self.programs.mtp:
            mirrors["draft"] = self._drafts
        return mirrors

    def _upload_sched(self) -> None:
        """Push host scheduler mirrors to device in ONE batched transfer —
        twelve per-array device_puts are twelve round trips, and this
        runs on every
        post-wake / post-admission chunk (bucketed path) / exact-edge
        packed step. The FULL upload — [b, vocab] counts and bias
        included — is the packed path's dirty-edge fallback; between
        dirty edges packed engines refresh only the small per-slot
        mirrors (_upload_sched_rows)."""
        with tracing.phase("sched.upload", self.chunk_in_flight) as ph:
            mirrors = self._sched_mirrors()
            nbytes = sum(a.nbytes for a in mirrors.values())
            ph.set(
                which="full", bytes=nbytes,
                why="dirty" if self._dirty else "no_dev",
            )
            self.step_h2d_bytes[self._h2d_path()] += nbytes
            self._dev = jax.device_put(mirrors, self._sched_sharding())
            self._dirty = False
            self._rows_stale = False
            # the pushed [b, vocab] rows are authoritative for every slot;
            # in-program fresh-slot zeroing would discard them
            self._fresh_slots.clear()

    def _upload_sched_rows(self) -> None:
        """Refresh ONLY the small per-slot mirrors on device — everything
        except the [b, vocab] counts/bias, which the packed path's
        programs maintain device-side between dirty edges. O(b ·
        pages_per_seq) bytes vs the full upload's O(b · vocab): this is
        what keeps a packed step's steady-state H2D at O(rows)."""
        with tracing.phase("sched.upload", self.chunk_in_flight) as ph:
            small = {
                k: v
                for k, v in self._sched_mirrors().items()
                if k not in self._VOCAB_MIRRORS
            }
            nbytes = sum(a.nbytes for a in small.values())
            ph.set(which="rows", bytes=nbytes, why="rows_stale")
            self.step_h2d_bytes[self._h2d_path()] += nbytes
            up = jax.device_put(small, self._sched_sharding())
            d = dict(self._dev)
            d.update(up)
            self._dev = d
            self._rows_stale = False

    def _upload_sched_table(self) -> None:
        """Refresh ONLY the device page table — the one piece of device
        state the mixed program reads besides counts/bias (its other
        per-slot inputs arrive as fresh host args each dispatch).
        Leaves _rows_stale SET: the next chunk dispatch still owes the
        full small-tier refresh (it reads lt/pos/budget/... from
        device), but back-to-back packed steps stop re-uploading
        mirrors nobody reads."""
        with tracing.phase("sched.upload", self.chunk_in_flight) as ph:
            pt = self._page_table
            ph.set(which="table", bytes=pt.nbytes, why="rows_stale")
            self.step_h2d_bytes[self._h2d_path()] += pt.nbytes
            d = dict(self._dev)
            d["pt"] = jax.device_put(pt, self._sched_sharding())
            self._dev = d

    def drop_device_sched_state(self) -> None:
        """Forget device scheduler arrays (sleep path). Host mirrors —
        including the per-slot RNG keys, re-synced after every chunk —
        remain the source of truth; the next chunk re-uploads them.
        Packed engines included: the device-resident counts/bias go with
        the client, and the host mirrors (kept exact — or, for a
        mid-prefill slot, MORE complete than the device copy, which may
        lack a cached prefix's counts while penalties are zero) rebuild
        everything in the next full upload."""
        self._dev = None
        self._dirty = True
        self._rows_stale = False

    def on_device_reacquire(self) -> None:
        """After a device-releasing sleep, the PJRT client was re-created:
        rebuild the engine's device-bound objects (its mesh) on the new
        device handles. Compiled programs re-lower lazily through the
        persistent compile cache; installed AOT executables belonged to
        the destroyed client and are dropped (the service re-validates
        the executable pool on wake)."""
        self.clear_executables()
        if self.mesh is not None:
            from .device import rebuild_mesh

            self.mesh = rebuild_mesh(
                tuple(self.mesh.axis_names), tuple(self.mesh.devices.shape)
            )
            # re-traces pin resident state against the NEW mesh (the old
            # one holds dead device handles)
            self.programs.mesh = self.mesh

    # -- request lifecycle --------------------------------------------------

    def add_request(
        self,
        prompt: Seq[int],
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_p: float = 1.0,
        stop_seqs: Seq[Seq[int]] = (),
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        on_tokens: Optional[Callable[[Request, List[int]], None]] = None,
        stop_watch: Optional[Callable[[int], bool]] = None,
        want_top_logprobs: bool = False,
        want_prompt_logprobs: bool = False,
        seed: Optional[int] = None,
        ignore_eos: bool = False,
        logit_bias: "Dict[int, float] | None" = None,
        submit_time: Optional[float] = None,
        trace: Optional[Any] = None,
    ) -> int:
        if not prompt:
            raise ValueError("empty prompt")
        if min(prompt) < 0 or max(prompt) >= self.cfg.model.vocab_size:
            # out-of-range ids would be silently clamped by the embedding
            # gather into garbage output; the HTTP layer pre-clamps, but a
            # request racing a model hot-swap can carry the OLD vocab
            raise ValueError("prompt token id outside vocab")
        if seed is not None and not (-(2**63) <= int(seed) < 2**63):
            # would overflow jax.random.key at admission, inside the
            # engine loop where it can't be attributed to this request
            raise ValueError("seed must fit in a signed 64-bit integer")
        if self.lockstep is not None and logit_bias:
            # like penalties: the [vocab] bias row is too large for the
            # lockstep frame; followers would sample unbiased
            raise ValueError(
                "logit_bias is not supported for multi-host gangs"
            )
        logit_bias = validate_logit_bias(
            logit_bias, self.cfg.model.vocab_size
        )
        if self.lockstep is not None and (presence_penalty or frequency_penalty):
            # penalties need the token-count state, which is too large for
            # the lockstep frame; followers run with zero penalties only
            raise ValueError(
                "repetition penalties are not supported for multi-host gangs"
            )
        total = len(prompt) + max_new_tokens
        if total > self.cfg.seq_len:
            raise ValueError(
                f"prompt+generation {len(prompt)}+{max_new_tokens} exceeds "
                f"max_seq_len {self.cfg.seq_len}"
            )
        if PageAllocator.pages_needed(total, self.cfg.page_size) > self.cfg.num_pages - 1:
            raise ValueError(
                f"request needs {PageAllocator.pages_needed(total, self.cfg.page_size)} "
                f"pages but the pool only has {self.cfg.num_pages - 1}"
            )
        req = Request(
            seq_id=self._next_seq_id,
            prompt=list(prompt),
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_p=float(top_p),
            stop_seqs=tuple(tuple(int(t) for t in s) for s in stop_seqs),
            presence_penalty=float(presence_penalty),
            frequency_penalty=float(frequency_penalty),
            on_tokens=on_tokens,
            stop_watch=stop_watch,
            want_top_logprobs=want_top_logprobs,
            want_prompt_logprobs=want_prompt_logprobs,
            seed=seed,
            ignore_eos=ignore_eos,
            logit_bias=logit_bias or {},
            trace=trace,
        )
        if submit_time is not None:
            # the HTTP layer's enqueue time, not this (possibly later)
            # engine-thread admission: queue-wait and TTFT then cover the
            # whole server-side wait, including the pre-engine pending list
            req.submit_time = submit_time
        self._next_seq_id += 1
        self._waiting.append(req)
        return req.seq_id

    def new_seq_id(self) -> int:
        """Mint a fresh local sequence id. Besides add_request, the
        migration import path uses this to re-key foreign Request
        objects before seating them — two engines' id spaces are
        unrelated and a collision would cross-wire futures."""
        sid = self._next_seq_id
        self._next_seq_id += 1
        return sid

    def _init_slot_key(self, req: Request) -> None:
        if req.rng_key_data is not None:
            # migrated-in seed-None request: the exporter pinned the
            # exact key its own admission would have derived
            self._slot_keys[req.slot] = np.asarray(
                req.rng_key_data, dtype=np.uint32
            )
            return
        # Three tiny programs and a read-back, on the HOST's backend: the
        # chip runs programs in launch order, so on it the read-back of a
        # step's second admission would return when the first prompt's
        # prefill has ended (_prefill_waiting). Threefry is integer
        # arithmetic: the key is the same bit for bit wherever it is made.
        with jax.default_device(_host_device()):
            if req.seed is not None:
                k = jax.random.key(int(req.seed))
            else:
                k = jax.random.fold_in(
                    jax.random.key(self._seed + 1), req.seq_id
                )
            self._slot_keys[req.slot] = np.asarray(jax.random.key_data(k))

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _try_admit(
        self, req: Request, prefill_unfetched: bool = False
    ) -> bool:
        """``_admit`` of the head of the waiting queue as the scheduler
        loop calls it: the ``sched.admit`` phase, and the count of steps
        in which the head was refused (a refusal ends a step's
        admissions, so there is at most one a step). ``prefill_unfetched``
        says the step's previous prefill is still running on the device
        (``_prefill_waiting``): the admission then holds nothing back."""
        with tracing.phase(
            "sched.admit", prefill_unfetched or self.chunk_in_flight
        ) as ph:
            admitted = self._admit(req)
            if admitted:
                ph.set(admitted=1)
            else:
                tracing.count_admit_blocked()
                ph.set(
                    admitted=0,
                    blocked="slots" if self._free_slot() is None else "pages",
                )
        return admitted

    def _admit(self, req: Request) -> bool:
        slot = self._free_slot()
        if slot is None:
            return False
        # a blocked request re-attempts every engine step: skip the whole
        # match+alloc dance until allocator or cache state actually moved.
        # Keyed on mutation counters, not sizes: an evict+register of equal
        # sizes changes what is matchable without moving either count.
        state = (
            self.allocator.version,
            self.prefix_cache.version if self.prefix_cache else 0,
        )
        if getattr(req, "_blocked_state", None) == state:
            return False
        total = len(req.prompt) + req.max_new_tokens
        need = PageAllocator.pages_needed(total, self.cfg.page_size)
        shared: List[int] = []
        hashes: List[str] = []
        if self.prefix_cache is not None and not req.want_prompt_logprobs:
            shared, req.cached_tokens, hashes = self.prefix_cache.match(
                req.prompt
            )
            # hold the shared pages BEFORE allocating: eviction inside the
            # allocation path must not reclaim what we just matched
            self.prefix_cache.acquire(shared)
        try:
            own = self._alloc_pages(need - len(shared))
        except OutOfPages:
            if self.prefix_cache is not None and shared:
                self.allocator.free(self.prefix_cache.release(shared))
            req.cached_tokens = 0
            req._blocked_state = (
                self.allocator.version,
                self.prefix_cache.version if self.prefix_cache else 0,
            )
            return False
        req.pages = shared + own
        req.shared_pages = len(shared)
        req._prefix_hashes = hashes
        if self.prefix_cache is not None:
            # the sequence's own reference for its non-shared pages (the
            # shared ones were acquired above); hit stats only now that
            # admission actually succeeded
            self.prefix_cache.acquire(own)
            self.prefix_cache.commit(hashes)
        req.slot = slot
        if req.first_sched_time is None:
            # every admission path (bucketed prefill, packed segments,
            # echo fallback) funnels through here: the one stamp that
            # closes the queue-wait window
            req.first_sched_time = time.monotonic()
            if req.trace is not None:
                req.trace.add(
                    "request.queue",
                    req.submit_time,
                    req.first_sched_time,
                    depth=len(self._waiting),
                )
        self._slots[slot] = req
        self._init_slot_key(req)
        self._eos_on[slot] = 0 if req.ignore_eos else 1
        self._bias[slot] = 0.0
        for t, v in req.logit_bias.items():
            self._bias[slot, t] = v
        row = np.zeros((self.cfg.pages_per_seq,), dtype=np.int32)
        row[: len(req.pages)] = req.pages
        self._page_table[slot, : row.size] = row
        # penalties count prompt tokens too (OpenAI "text so far")
        self._token_counts[slot] = 0
        np.add.at(self._token_counts[slot], req.prompt, 1)
        self._pres[slot] = req.presence_penalty
        self._freqs[slot] = req.frequency_penalty
        # sampling mirrors at admission (the packed program samples from
        # the slot-indexed mirrors mid-prefill; the bucketed prefill
        # re-writes the same values after it runs)
        self._temps[slot] = req.temperature
        self._topps[slot] = req.top_p
        if self._packed:
            # the small mirrors re-upload on the rows edge; counts/bias
            # device rows are handled by the packed step itself (zeroed
            # in-program for fresh slots, full re-upload on exact edges
            # — _step_packed decides which). The echo fallback's
            # _run_prefill still forces the full dirty edge.
            self._rows_stale = True
        else:
            self._dirty = True
        return True

    def _alloc_pages(self, n: int) -> List[int]:
        """Allocate, evicting LRU cache-only prefix pages under pressure."""
        try:
            return self.allocator.alloc(n)
        except OutOfPages:
            if self.prefix_cache is None:
                raise
            evicted = self.prefix_cache.evict(n - self.allocator.available)
            if not evicted:
                raise
            self.allocator.free(evicted)
            return self.allocator.alloc(n)

    def _prefill_bucket(self, n: int) -> int:
        return prefill_bucket(n, self.cfg.seq_len)

    def _run_suffix_segment(
        self, req: Request, start_pos: int, seg: List[int], temp, topp,
        counts_row, pres, freq, final: bool,
    ):
        """One prefill segment via the continue program: scatter the
        segment's KV, attend over everything already in the pages. Used by
        prefix-cache hits AND chunked prefill (a segment at start 0 works
        too: its own KV is scattered before the paged attention).

        Only the FINAL segment advances the RNG key: non-final segments'
        in-program sample is discarded, so a chunked prefill consumes
        exactly one key split — the same as an unchunked one — and
        temperature>0 outputs are identical either way."""
        table = self._page_table[req.slot : req.slot + 1]
        bucket = self._prefill_bucket(len(seg))
        self.pad_waste_bytes["bucketed"] += (
            (bucket - len(seg)) * self._pad_token_bytes
        )
        self.dispatch_tokens["bucketed"] += len(seg)
        self._count_forward(start_pos, len(seg), bucket)
        self._count_segment(start_pos, suffix_rows=bucket)
        self._count_passes(1)
        tokens = np.zeros((1, bucket), dtype=np.int32)
        tokens[0, : len(seg)] = seg
        # next prompt token at each segment position (prompt-logprob
        # targets); the final position of the final segment has none
        targets = np.zeros((1, bucket), dtype=np.int32)
        nxt = req.prompt[start_pos + 1 : start_pos + len(seg) + 1]
        targets[0, : len(nxt)] = nxt
        if final and self.programs.mtp:
            # the prediction module's token after the prompt's last is the
            # one this program samples (ProgramSet._make_suffix_prefill)
            targets[0, len(seg) - 1] = -1
        start = np.array([start_pos], dtype=np.int32)
        seg_lens = np.array([len(seg)], dtype=np.int32)
        if self.lockstep is not None:
            self.lockstep.prefill_suffix(
                req, bucket, start_pos, len(seg), advance_key=final,
                want_plp=req.want_prompt_logprobs,
            )
        self.step_h2d_bytes["bucketed"] += (
            tokens.nbytes + targets.nbytes + start.nbytes + seg_lens.nbytes
            + table.nbytes + temp.nbytes + topp.nbytes + counts_row.nbytes
            + pres.nbytes + freq.nbytes + self._slot_keys[req.slot].nbytes
            + self._bias[req.slot : req.slot + 1].nbytes
        )
        tok, lp, av, ai, plp, cache, new_key, *draft = self._call_program(
            "suffix_plp" if req.want_prompt_logprobs else "suffix",
            bucket,
            self.params,
            tokens,
            targets,
            start,
            seg_lens,
            self.pool.as_tuple(),
            table,
            temp,
            topp,
            counts_row,
            pres,
            freq,
            self._slot_keys[req.slot],
            self._bias[req.slot : req.slot + 1],
        )
        self.pool.replace(cache)
        # key sync is the caller's: it batches it with the other host reads
        return tok, lp, av, ai, plp, new_key, draft

    def _run_prefill(self, req: Request) -> None:
        """One prompt, dispatched and finished at once: the packed
        path's echo fallback. The bucketed loop runs the two halves one
        request apart (``_prefill_waiting``)."""
        self._finish_prefill(self._dispatch_prefill(req))

    def _dispatch_prefill(
        self, req: Request, prefill_unfetched: bool = False
    ) -> Tuple[Request, list, list]:
        """The dispatch half of a prompt: every program of it (one cold
        segment, or the suffix segments in order) is on the device's
        queue when this returns and nothing of it has been read. Returns
        what ``_finish_prefill`` fetches. ``prefill_unfetched`` says the
        step's previous prompt is still running there: the pool threads
        this one behind it, and the host's work here holds nothing back."""
        overlapped = prefill_unfetched or self.chunk_in_flight
        with tracing.phase("sched.prefill_dispatch", overlapped) as ph:
            if prefill_unfetched:
                tracing.count_prefill_overlapped()
            ph.set(overlapped=int(prefill_unfetched))
            n = len(req.prompt)
            temp = np.asarray([req.temperature], dtype=np.float32)
            topp = np.asarray([req.top_p], dtype=np.float32)
            counts_row = self._token_counts[req.slot : req.slot + 1]
            pres = np.asarray([req.presence_penalty], dtype=np.float32)
            freq = np.asarray([req.frequency_penalty], dtype=np.float32)
            k = req.cached_tokens
            limit = self.cfg.max_prefill_tokens or (n - k)
            # (device ref, entries taken) a segment, if the request asks
            plp_parts = []
            if k == 0 and n <= limit:
                # single cold segment: the flash-style causal program
                table = self._page_table[req.slot : req.slot + 1]
                bucket = self._prefill_bucket(n)
                ph.set(program="prefill", bucket=bucket, prompt_tokens=n)
                self.pad_waste_bytes["bucketed"] += (
                    (bucket - n) * self._pad_token_bytes
                )
                self.dispatch_tokens["bucketed"] += n
                self._count_forward(0, n, bucket)
                self._count_segment(0)
                self._count_passes(1)
                tokens = np.zeros((1, bucket), dtype=np.int32)
                tokens[0, :n] = req.prompt
                seq_lens = np.array([n], dtype=np.int32)
                if self.lockstep is not None:
                    self.lockstep.prefill(
                        req, bucket, want_plp=req.want_prompt_logprobs
                    )
                self.step_h2d_bytes["bucketed"] += (
                    tokens.nbytes + seq_lens.nbytes + table.nbytes + temp.nbytes
                    + topp.nbytes + counts_row.nbytes + pres.nbytes + freq.nbytes
                    + self._slot_keys[req.slot].nbytes
                    + self._bias[req.slot : req.slot + 1].nbytes
                )
                tok, lp, av, ai, plp, cache, new_key, *draft = self._call_program(
                    "prefill_plp" if req.want_prompt_logprobs else "prefill",
                    bucket,
                    self.params,
                    tokens,
                    seq_lens,
                    self.pool.as_tuple(),
                    table,
                    temp,
                    topp,
                    counts_row,
                    pres,
                    freq,
                    self._slot_keys[req.slot],
                    self._bias[req.slot : req.slot + 1],
                )
                self.pool.replace(cache)
                if req.want_prompt_logprobs:
                    # device refs only; fetched in the single batched sync below
                    plp_parts = [(plp, n - 1)]
            else:
                # prefix-cache hit and/or chunked prefill: run [k, n) through
                # the continue program in segments of <= limit tokens; only the
                # final segment's sample is consumed
                pos = k
                ph.set(
                    program="suffix", prompt_tokens=n, cached_tokens=k,
                    bucket=self._prefill_bucket(min(limit, n - k)),
                )
                while pos < n:
                    seg = req.prompt[pos : min(n, pos + limit)]
                    final = pos + len(seg) >= n
                    (
                        tok, lp, av, ai, plp, seg_key, draft,
                    ) = self._run_suffix_segment(
                        req, pos, seg, temp, topp, counts_row, pres, freq,
                        final=final,
                    )
                    if final:
                        new_key = seg_key
                    if req.want_prompt_logprobs:
                        # entries predict prompt[pos+1 .. pos+len(seg)]; the
                        # final segment's last entry predicts nothing
                        take = len(seg) if not final else len(seg) - 1
                        plp_parts.append((plp, take))
                    pos += len(seg)
            if self.prefix_cache is not None:
                # the full prompt pages now hold prompt KV: make them reusable
                self.prefix_cache.register(
                    req.prompt,
                    req.pages,
                    req.shared_pages,
                    known_hashes=getattr(req, "_prefix_hashes", ()),
                )
            # ONE batched host sync for everything the emit needs — separate
            # np.asarray calls are separate round trips on high-latency links,
            # and this is the tail of every TTFT measurement. Prompt-logprob
            # rows (one per prefill segment) ride the same fetch.
            # (a program that drafts returns its first draft: one more read)
            fetch = [tok, lp, new_key, *draft]
            if req.want_top_logprobs:
                fetch += [av, ai]
            if req.want_prompt_logprobs:
                fetch += [p for p, _ in plp_parts]
        return req, fetch, plp_parts

    def _finish_prefill(
        self,
        dispatched: Tuple[Request, list, list],
        prefill_unfetched: bool = False,
    ) -> None:
        """The finish half: the one blocking read of a dispatched
        prompt's results, then its first token (and with it the slot's
        key and sampling mirrors). ``prefill_unfetched`` says the NEXT
        prompt of the step is already on the device's queue."""
        req, fetch, plp_parts = dispatched
        n = len(req.prompt)
        overlapped = prefill_unfetched or self.chunk_in_flight
        with tracing.phase("sched.prefill_fetch", overlapped):
            vals = list(jax.device_get(tuple(fetch)))
        with tracing.phase("sched.emit", overlapped) as ph:
            tok_h, lp_h, key_h = vals[:3]
            vals = vals[3:]
            if self.programs.mtp:
                self._drafts[req.slot] = int(vals.pop(0)[0])
            alts = None
            if req.want_top_logprobs:
                av_h, ai_h = vals[:2]
                vals = vals[2:]
                alts = [
                    (int(ai_h[0, j]), float(av_h[0, j]))
                    for j in range(av_h.shape[1])
                ]
            if req.want_prompt_logprobs:
                req.prompt_logprobs = [None]  # nothing precedes token 0
                for row, (_, take) in zip(vals, plp_parts):
                    req.prompt_logprobs.extend(
                        float(row[0][i]) for i in range(take)
                    )
            self._slot_keys[req.slot] = key_h
            req.pos = n  # position of the token to place
            self._emit(req, int(tok_h[0]), float(lp_h[0]), alts, placed=False)
            self._temps[req.slot] = req.temperature
            self._topps[req.slot] = req.top_p
            self._dirty = True
            ph.set(tokens=1, finished=int(req.done))

    def _emit(
        self,
        req: Request,
        token: int,
        logprob: float = 0.0,
        alts: Optional[list] = None,
        placed: bool = True,
    ) -> None:
        """One token: a run of one (a prefill's first token, a packed
        step's row, a speculation round's tokens), counted and streamed
        at once."""
        self._emit_run(req, [token], [logprob], [alts or []], placed)
        if req.slot >= 0:
            # host counts mirror the device copy the chunk program updates
            # (stop-stripped tokens stay counted on both sides)
            self._token_counts[req.slot, token] += 1
        self._stream(req)

    def _emit_run(
        self,
        req: Request,
        toks: List[int],
        lps: List[float],
        alts: Optional[List[list]] = None,
        placed: bool = True,
    ) -> int:
        """Emit one request's run: the leading tokens of ``toks`` (what its
        slot sampled in one drained chunk) up to where the request ends —
        its budget, the first eos unless ``ignore_eos``, a stop asked for
        from outside, a stop sequence completing. The output lists and the
        slot's small mirrors are written once. Left to the caller, which
        knows whether it has one token or a chunk's worth: the slot's
        token counts, and ``_stream``, which hands the newly safe tokens
        to the streaming hook in one call. ``placed`` says each token of
        the run was a decode step that wrote its position (not so a
        prefill's first token, sampled and yet to be placed). Returns the
        run's length, the tokens a stop sequence strips included.
        Per-token Python runs only for a request that carries stop
        sequences or a ``stop_watch``."""
        if req.first_token_time is None:
            req.first_token_time = time.monotonic()
            if (
                req.trace is not None
                and req.first_sched_time is not None
                and not req.out_tokens
            ):
                # out_tokens non-empty with no first_token_time = a
                # migrated-in mid-decode request: its prefill happened
                # on the source; don't mislabel the re-seat window
                req.trace.add(
                    "request.prefill",
                    req.first_sched_time,
                    req.first_token_time,
                    prompt_tokens=len(req.prompt),
                    cached_tokens=req.cached_tokens,
                    packed=bool(self._packed),
                )
        out = req.out_tokens
        n = max(1, min(len(toks), req.max_new_tokens - len(out)))
        reason = "length" if len(out) + n >= req.max_new_tokens else ""
        if not req.ignore_eos and not self._eos_ids.isdisjoint(toks[:n]):
            n = next(i for i, t in enumerate(toks) if t in self._eos_ids) + 1
            reason = "stop"
        if req.stop_requested:
            n, reason = 1, "stop"
        strip = 0
        if req.stop_seqs or req.stop_watch is not None:
            n, reason, strip = self._run_end(req, toks, n, reason)
        run = toks[:n]
        out.extend(run)
        req.out_logprobs.extend(lps[:n])
        req.out_top_logprobs.extend(
            alts[:n] if alts is not None else [[] for _ in run]
        )
        self.total_tokens_emitted += n
        if strip:
            # OpenAI semantics: finish on the stop sequence and strip it
            # (its head may have come with an earlier run, held back)
            del out[-strip:]
            del req.out_logprobs[-strip:]
            del req.out_top_logprobs[-strip:]
        if placed:
            req.pos += n
        slot = req.slot
        if slot >= 0:
            self._positions[slot] = req.pos
            self._last_tokens[slot] = run[-1]
            # keep the budget mirror exact: a dirty re-upload with a
            # stale budget would un-freeze finished slots on device
            self._budgets[slot] = req.max_new_tokens - len(out)
        if reason:
            req.done = True
            req.finish_reason = reason
        return n

    def _run_end(
        self, req: Request, toks: List[int], n: int, reason: str
    ) -> Tuple[int, str, int]:
        """Where a run of at most ``n`` tokens ends for a request that has
        to be asked token by token: at the token that completes a stop
        sequence (returned with the sequence's length, to strip), or at
        the token after the one at which ``stop_watch`` asked for a stop
        (``stop_requested`` finishes a request at its NEXT token). The
        watch sees each token once, when the stop-sequence hold-back
        releases it, as the hook would have streamed it."""
        seqs, watch = req.stop_seqs, req.stop_watch
        cand = req.out_tokens + toks[:n]
        start = len(req.out_tokens)
        seen = start - _stop_holdback(req.out_tokens, seqs)
        i = 0
        while i < n:
            end = start + i + 1
            for seq in seqs:
                if end >= len(seq) and tuple(cand[end - len(seq):end]) == seq:
                    return i + 1, "stop", len(seq)
            if watch is not None:
                last = i == n - 1
                safe = end if last and reason else end - _stop_holdback(
                    cand[:end], seqs
                )
                for t in cand[seen:safe]:
                    if watch(t):
                        req.stop_requested = True
                seen = max(seen, safe)
                if req.stop_requested and not last:
                    n, reason = i + 2, "stop"
            i += 1
        return n, reason, 0

    def _stream(self, req: Request) -> None:
        """Deliver the newly safe tokens to the streaming hook, in one call.

        Tokens forming a suffix of the output that is a proper prefix of a
        stop sequence are held back — they may yet be stripped. On finish,
        everything that survived stripping is flushed; consumers see
        `req.done` only on the delivery that holds the final token (the
        SSE writer keys its terminator on it)."""
        if req.on_tokens is None:
            return
        end = len(req.out_tokens)
        if req.stop_seqs and not req.done:
            end -= _stop_holdback(req.out_tokens, req.stop_seqs)
        if end > req.streamed:
            tracing.count_emit_delivery(end - req.streamed)
            deliver_tokens(req, end)

    def _retire(self, req: Request) -> None:
        if self.prefix_cache is not None:
            self.allocator.free(self.prefix_cache.release(req.pages))
        else:
            self.allocator.free(req.pages)
        self._slots[req.slot] = None
        self._page_table[req.slot, : self.cfg.pages_per_seq] = 0
        self._positions[req.slot] = 0
        self._last_tokens[req.slot] = 0
        self._temps[req.slot] = 0.0
        self._topps[req.slot] = 1.0
        self._pres[req.slot] = 0.0
        self._freqs[req.slot] = 0.0
        self._token_counts[req.slot] = 0
        self._budgets[req.slot] = 0
        self._drafts[req.slot] = 0
        self._slot_keys[req.slot] = 0
        self._eos_on[req.slot] = 1
        self._bias[req.slot] = 0.0
        req.slot = -1
        if self._packed:
            # a retired slot's device counts/bias rows go stale-but-
            # frozen: the chunk program never samples a zero-budget slot
            # into anything the host reads, and the next packed
            # admission into the slot zeroes the rows in-program
            # (fresh_on) — no O(b·vocab) re-upload per retire edge
            self._rows_stale = True
        else:
            self._dirty = True

    # -- token-packed mixed-batch serving (cfg.packed_serving) ---------------

    def _any_prefilling(self) -> bool:
        return any(
            r is not None and r.prefilling and not r.done
            for r in self._slots
        )

    def _packed_shapes(self) -> List[int]:
        return packed_budget_shapes(self.cfg)

    def _step_packed(self, finished: List[Request]) -> bool:
        """One token-packed mixed-batch step: pack a decode row per
        running sequence plus prefill segments from the in-flight and
        waiting queues into the flat [token_budget] buffer, dispatch the
        ONE mixed program, and emit at most one token per sequence.

        Returns False without dispatching when no prefill segment could
        be packed (the waiting queue is blocked on slots/pages) — the
        caller then runs the fused decode chunk instead, so a blocked
        queue never degrades decode to one token per dispatch.

        Packing layout (the ragged kernel's contract, ops/pallas/
        ragged.py): each sequence's rows are contiguous with consecutive
        positions and start on a RAGGED_BLOCK boundary; alignment gaps
        and the buffer tail are padding rows (row_slot = -1) the model
        computes but nobody reads.
        """
        qb = self._pack_align
        T = self._token_budget
        b = self.cfg.max_batch
        tokens = np.zeros((T,), dtype=np.int32)
        row_slot = np.full((T,), -1, dtype=np.int32)
        positions = np.zeros((T,), dtype=np.int32)
        #: rows whose token joins its slot's device count row BEFORE the
        #: sampling tail: streamed prompt tokens (decode rows' tokens
        #: were already counted when they were emitted)
        count_row = np.zeros((T,), dtype=np.int32)
        sample_rows = np.zeros((b,), dtype=np.int32)
        sample_on = np.zeros((b,), dtype=np.int32)
        rows_used = 0
        decode_reqs: List[Request] = []
        segments: List[Tuple[Request, int, bool]] = []
        seg_cap = self.cfg.max_prefill_tokens or T

        def pack_segment(req: Request) -> bool:
            nonlocal rows_used
            room = T - rows_used
            if room < qb:
                return False
            take = min(len(req.prompt) - req.pos, seg_cap, room)
            if take <= 0:
                return False
            start = rows_used
            tokens[start : start + take] = req.prompt[
                req.pos : req.pos + take
            ]
            row_slot[start : start + take] = req.slot
            positions[start : start + take] = np.arange(
                req.pos, req.pos + take, dtype=np.int32
            )
            count_row[start : start + take] = 1
            final = req.pos + take >= len(req.prompt)
            if final:
                # the segment's last row predicts the first generated token
                sample_rows[req.slot] = start + take - 1
                sample_on[req.slot] = 1
            segments.append((req, take, final))
            rows_used += -(-take // qb) * qb
            return True

        # 1. one decode row per running sequence — decode NEVER stalls
        #    behind prefill; each row owns an aligned block (a kernel
        #    block holds exactly one sequence)
        for slot, req in enumerate(self._slots):
            if req is None or req.done or req.prefilling:
                continue
            tokens[rows_used] = self._last_tokens[slot]
            row_slot[rows_used] = slot
            positions[rows_used] = req.pos
            sample_rows[slot] = rows_used
            sample_on[slot] = 1
            decode_reqs.append(req)
            rows_used += qb

        # 2. advance in-flight chunked prefills (slot order), one segment
        #    each per step (max_prefill_tokens bounds segment length)
        for req in self._slots:
            if req is not None and req.prefilling and not req.done:
                pack_segment(req)

        # 3. admit waiting requests into the remaining budget
        while self._waiting and T - rows_used >= qb:
            req = self._waiting[0]
            if req.want_prompt_logprobs:
                # echo requests need the full-bucket prompt-logprob
                # scoring variants: bucketed fallback, same step
                if not self._try_admit(req):
                    break
                self._waiting.pop(0)
                self._run_prefill(req)
                if req.done:
                    self._retire(req)
                    finished.append(req)
                continue
            if not self._try_admit(req):
                break
            self._waiting.pop(0)
            req.prefilling = True
            req.pos = req.cached_tokens
            # Device-resident counts: the host mirror follows the
            # STREAMING semantics the mixed program implements — cached-
            # prefix counts now (those tokens never enter the buffer),
            # packed rows as they stream (below). _admit's full-prompt
            # count is rewritten; the echo fallback above keeps it.
            self._token_counts[req.slot] = 0
            if req.cached_tokens:
                np.add.at(
                    self._token_counts[req.slot],
                    req.prompt[: req.cached_tokens], 1,
                )
            if req.logit_bias or (
                (req.presence_penalty or req.frequency_penalty)
                and req.cached_tokens
            ):
                # exact edges the program can't reproduce from the
                # buffer: a non-zero bias row, or penalties over a
                # cached prefix whose tokens never stream — fall back to
                # the full mirror re-upload for this step
                self._dirty = True
            else:
                self._fresh_slots.add(req.slot)
            pack_segment(req)

        if not segments:
            # nothing but decode rows: the fused chunk path serves the
            # running batch better (decode_chunk tokens per dispatch)
            return False

        # dispatch at the smallest compiled buffer shape that fits (one
        # or two shapes ever compile; _packed_shapes), against the
        # device-resident page table sliced IN-PROGRAM to the power-of-
        # two-ish width the step's longest sequence needs — bit-exact,
        # and it bounds the reference twin's gather by live context
        # instead of max_seq (mixed_bucket)
        shape = next(s for s in self._packed_shapes() if s >= rows_used)
        vmask = row_slot[:shape] >= 0
        valid = int(vmask.sum())
        max_kv = int(positions[:shape][vmask].max()) + 1
        kvp = kv_pages_bucket(
            max_kv, self.cfg.page_size, self.cfg.pages_per_seq
        )
        prefill_tokens = sum(t for _, t, _ in segments)
        self.packed_steps += 1
        self.packed_tokens_total += valid
        self.pad_waste_bytes["packed"] += (
            (shape - valid) * self._pad_token_bytes
        )
        self.dispatch_tokens["packed"] += valid
        # Scheduler state sync, cheapest sufficient tier: a dirty edge
        # (exact-count/bias admission, echo fallback, sleep/wake drop)
        # pushes the full mirrors — and makes the in-program fresh-slot
        # zeroing moot; otherwise only the small per-slot mirrors
        # refresh (the mixed program needs the page table rows the
        # admissions just wrote). Ordering matters: the upload must
        # precede the host-side streamed-count adds below, because the
        # program pre-adds the same rows on device either way.
        fresh_on = np.zeros((b,), dtype=np.int32)
        if self._dirty or self._dev is None:
            self._upload_sched()
        else:
            if self._fresh_slots:
                fresh_on[list(self._fresh_slots)] = 1
            if self._rows_stale:
                self._upload_sched_table()
        d = self._dev
        self.step_h2d_bytes["packed"] += (
            tokens[:shape].nbytes + row_slot[:shape].nbytes
            + positions[:shape].nbytes + count_row[:shape].nbytes
            + sample_rows.nbytes + sample_on.nbytes + fresh_on.nbytes
            + self._temps.nbytes + self._topps.nbytes + self._pres.nbytes
            + self._freqs.nbytes + self._slot_keys.nbytes
        )
        self.last_step_stats = {
            "mode": "packed",
            "rows": shape,
            "tokens": valid,
            "pad_rows": shape - valid,
            "decode_rows": len(decode_reqs),
            "prefill_tokens": prefill_tokens,
        }
        with tracing.span(
            "step.packed", rows=shape, tokens=valid,
            decode_rows=len(decode_reqs), prefill_tokens=prefill_tokens,
        ):
            with tracing.phase("sched.prefill_dispatch", False) as ph:
                ph.set(
                    program="mixed", bucket=shape,
                    prompt_tokens=prefill_tokens,
                )
                self._count_passes(1)
                tok, lp, av, ai, cache, counts_dev, bias_dev, skeys = (
                    self._call_program(
                        "mixed", mixed_bucket(shape, kvp),
                        self.params,
                        tokens[:shape],
                        row_slot[:shape],
                        positions[:shape],
                        count_row[:shape],
                        sample_rows,
                        sample_on,
                        fresh_on,
                        self.pool.as_tuple(),
                        d["pt"],
                        self._temps,
                        self._topps,
                        d["counts"],
                        self._pres,
                        self._freqs,
                        self._slot_keys,
                        d["bias"],
                    )
                )
                self.pool.replace(cache)
                # the program consumed (donated) and re-emitted the device-
                # resident mirrors; they stay the between-dispatch truth
                d["counts"] = counts_dev
                d["bias"] = bias_dev
                self._fresh_slots.clear()
            with tracing.phase("sched.prefill_fetch", False):
                # ONE batched host sync for the whole step's emits
                tok_h, lp_h, av_h, ai_h, keys_h = jax.device_get(
                    (tok, lp, av, ai, skeys)
                )
        with tracing.phase("sched.emit", False) as ph:
            emitted = self.total_tokens_emitted
            before = len(finished)
            # non-sampling slots' keys came back unchanged (in-program where)
            self._slot_keys[:] = keys_h
            # host count mirrors absorb the streamed prompt rows exactly as
            # the program pre-added them on device (req.pos still pre-step)
            for req, take, _final in segments:
                if req.slot >= 0:
                    np.add.at(
                        self._token_counts[req.slot],
                        req.prompt[req.pos : req.pos + take], 1,
                    )

            def alts_for(req: Request, slot: int):
                if not req.want_top_logprobs:
                    return None
                return [
                    (int(ai_h[slot, j]), float(av_h[slot, j]))
                    for j in range(av_h.shape[1])
                ]

            # prefill segments advance; final segments emit their first token
            for req, take, final in segments:
                if req.done:  # aborted mid-step: pages already freed
                    continue
                slot = req.slot
                req.pos += take
                if not final:
                    continue
                req.prefilling = False
                if self.prefix_cache is not None:
                    # the full prompt's KV is now in pages: make it reusable
                    self.prefix_cache.register(
                        req.prompt, req.pages, req.shared_pages,
                        known_hashes=getattr(req, "_prefix_hashes", ()),
                    )
                self._emit(
                    req, int(tok_h[slot]), float(lp_h[slot]),
                    alts_for(req, slot), placed=False,
                )
                if req.done:
                    self._retire(req)
                    finished.append(req)
            # decode rows emit one token each
            for req in decode_reqs:
                if req.done:
                    continue
                slot = req.slot
                self._emit(
                    req, int(tok_h[slot]), float(lp_h[slot]),
                    alts_for(req, slot),
                )
                if req.done:
                    self._retire(req)
                    finished.append(req)
            ph.set(
                tokens=self.total_tokens_emitted - emitted,
                finished=len(finished) - before,
            )
        # the [b, vocab] device mirrors are already exact (the program
        # maintained them); only the small per-slot mirrors (last
        # tokens, positions, budgets — advanced by the emits above)
        # need the next dispatch to refresh them
        self._rows_stale = True
        return True

    # -- speculative (n-gram / prompt-lookup) decoding -----------------------

    def _spec_candidate(self) -> Optional[Request]:
        """Speculation engages only where it is exact and simple: exactly
        one greedy (temp=0, full top-p) sequence in flight, nothing
        waiting, no gang lockstep."""
        if self.cfg.speculative_ngram <= 0 or self.lockstep is not None:
            return None
        if self._waiting:
            return None
        # a mid-prefill slot (packed serving) has no sampled token yet —
        # its last-token mirror is not a valid speculation context
        active = [
            r
            for r in self._slots
            if r is not None and not r.done and not r.prefilling
        ]
        if len(active) != 1:
            return None
        r = active[0]
        # only transforms that shift the argmax gate exactness: at
        # temperature 0 sampling is the full-vocab argmax regardless of
        # top_p, and streaming (on_tokens) already receives multi-token
        # bursts from the chunk path — but repetition penalties DO move
        # the argmax, and the verify program doesn't apply them
        if (
            r.temperature != 0.0
            or r.presence_penalty != 0.0
            or r.frequency_penalty != 0.0
            or r.logit_bias
        ):
            return None
        return r

    def _propose_ngram(self, req: Request, k: int) -> List[int]:
        """Prompt-lookup proposal: find the most recent PREVIOUS occurrence
        of the context's trailing m-gram (m = 3, 2) and propose the tokens
        that followed it."""
        # bounded lookback: an unbounded backward scan (or a full-context
        # concat) is O(context) host work per decode step — build only the
        # trailing window (vLLM caps its ngram lookup the same way)
        lookback = 1024 + k
        out = req.out_tokens
        if len(out) >= lookback:
            ctx = out[-lookback:]
        else:
            ctx = req.prompt[-(lookback - len(out)):] + out
        for m in (3, 2):
            if len(ctx) <= m:
                continue
            tail = ctx[-m:]
            for i in range(len(ctx) - m - 1, -1, -1):
                if ctx[i : i + m] == tail:
                    props = ctx[i + m : i + m + k]
                    if props:
                        return props
        return []

    def _spec_round(self, req: Request) -> bool:
        """One speculative verify round. Returns True if it ran (the caller
        skips the normal chunk step), False to fall back.

        Window [t0, q1..qk] runs through the verify program (the continue
        program + argmax): o[i] is the model's greedy token after
        window[:i+1]. Accept q_{i+1} while o[i] == q_{i+1}; the first
        mismatch's o is the corrected token, and a fully-accepted window
        yields o[k] as a bonus token — up to k+1 tokens per forward.
        Rejected tokens' KV stays in pages beyond `positions` where the
        attention mask never looks; it is overwritten as decoding reaches
        those positions."""
        k = min(
            self.cfg.speculative_ngram,
            req.max_new_tokens - len(req.out_tokens),
            self.cfg.seq_len - req.pos - 1,
        )
        if k <= 0:
            return False
        if self._spec_cooldown > 0:
            # acceptance-rate hysteresis: after a run of fully-rejected
            # rounds, speculation costs a verify forward per single token
            # (vs the fused chunk); back off to the chunk path for a while
            self._spec_cooldown -= 1
            return False
        props = self._propose_ngram(req, k)
        if not props:
            return False
        window = [int(self._last_tokens[req.slot])] + props
        bucket = self._prefill_bucket(len(window))
        tokens = np.zeros((1, bucket), dtype=np.int32)
        tokens[0, : len(window)] = window
        start = np.array([req.pos], dtype=np.int32)
        window_len = np.array([len(window)], dtype=np.int32)
        table = self._page_table[req.slot : req.slot + 1]
        self.step_h2d_bytes["bucketed"] += (
            tokens.nbytes + start.nbytes + window_len.nbytes + table.nbytes
        )
        # a verify forward is decode work: it is timed as a chunk of
        # len(window) steps (speculation never runs beside a chunk in flight)
        with tracing.phase("sched.chunk_dispatch", False) as ph:
            ph.set(T=len(window), live_slots=1, program="verify")
            self._count_passes(1)
            toks, lps_dev, avs_dev, ais_dev, cache = self._verify_fn(
                self.params, tokens, start, window_len,
                self.pool.as_tuple(), table,
            )
            self.pool.replace(cache)
        with tracing.phase("sched.chunk_fetch", False):
            # one batched host sync (4 separate np.asarray = 4 round trips)
            o, o_lp, o_av, o_ai = (
                x[0]
                for x in jax.device_get((toks, lps_dev, avs_dev, ais_dev))
            )
        self.spec_proposed += len(props)
        accepted = 0
        emitted: List[Tuple[int, float, list]] = []

        def _spec_alts(i):
            if not req.want_top_logprobs:
                return None
            return [
                (int(o_ai[i, j]), float(o_av[i, j]))
                for j in range(o_av.shape[1])
            ]

        for i, q in enumerate(props):
            if int(o[i]) != q:
                # corrected token
                emitted.append((int(o[i]), float(o_lp[i]), _spec_alts(i)))
                break
            accepted += 1
            emitted.append((q, float(o_lp[i]), _spec_alts(i)))
        else:
            i = len(props)
            emitted.append((int(o[i]), float(o_lp[i]), _spec_alts(i)))
        self.spec_accepted += accepted
        if accepted == 0:
            self._spec_miss_streak += 1
            if self._spec_miss_streak >= 4:
                self._spec_cooldown = 32
                self._spec_miss_streak = 0
        else:
            self._spec_miss_streak = 0
        with tracing.phase("sched.emit", False) as ph:
            for t, lp, alts in emitted:
                self._emit(req, t, lp, alts)
                if req.done:
                    break
            ph.set(tokens=len(emitted), finished=int(req.done))
        self._dirty = True  # device scheduler state is stale
        return True

    # -- the engine loop body ----------------------------------------------

    def step(self) -> List[Request]:
        """Admit + prefill waiting requests, then one decode *chunk* (up to
        ``decode_chunk`` fused steps) for the running batch. Returns requests
        that finished."""
        if self.params is None:
            raise EngineAsleep("engine state is offloaded (sleeping)")
        self.last_step_stats = None
        finished: List[Request] = list(self._orphan_finished)
        self._orphan_finished.clear()

        # Token-packed mixed-batch path (cfg.packed_serving): whenever
        # packable prefill work is pending, ONE mixed program carries
        # prefill segments AND a decode row per running sequence, then
        # the step FALLS THROUGH to the fused decode chunk below — the
        # same prefill-then-chunk step shape as the bucketed path, so
        # decode keeps its decode_chunk-per-dispatch fusion while
        # prompts neither serialize behind each other nor stall it (the
        # mixed step's decode rows are the no-stall bonus token). A
        # waiting queue blocked on slots/pages packs nothing and goes
        # straight to the chunk.
        packed_mode = self._packed and self.lockstep is None
        if packed_mode and (self._waiting or self._any_prefilling()):
            self._step_packed(finished)

        if not packed_mode:
            self._prefill_waiting(finished)

        # speculation never interleaves with an in-flight chunk: a verify
        # forward would race the chunk's decode of the same slot
        spec_req = self._spec_candidate() if self._inflight is None else None
        if spec_req is not None and self._spec_round(spec_req):
            if spec_req.done:
                self._retire(spec_req)
                finished.append(spec_req)
            return finished

        pipelined = self.cfg.pipeline_decode and self.lockstep is None
        if not pipelined:
            running = self._running()
            if running:
                finished.extend(
                    self._drain_chunk(self._dispatch_chunk(running))
                )
            return finished

        # Pipelined (double-buffered) decode: dispatch chunk k+1 BEFORE
        # reading chunk k's results, so the device computes k+1 while the
        # host fetches and emits k — hiding the dispatch/fetch round trip
        # that dominates decode on high-latency links (docs/perf.md).
        # Page-safety invariant: a chunk dispatched after a request's
        # finish became known never writes its slot (host finishes freeze
        # the budget mirror and mark it dirty, and a dirty state forces
        # drain-then-reupload ordering below), so a finished request's
        # pages may be written only by the ONE chunk already in flight —
        # its retire (page free / prefix-cache registration) is deferred
        # until that chunk drains (_defer_retire).
        if self._inflight is not None:
            running = self._running()
            nxt = None
            if running and not self._dirty and not self._waiting:
                # End-of-batch tail: when every running request's remaining
                # budget fits inside the chunk already in flight, that chunk
                # finishes them all (budget exhaustion is unconditional, eos
                # can only finish earlier) and a speculative chunk k+1 would
                # be fully frozen — skip it and drain-then-dispatch at this
                # boundary instead of burning a wasted chunk of device work
                # plus one chunk of tail latency.
                t_inflight = self._inflight[6]
                if any(
                    r.max_new_tokens - len(r.out_tokens) > t_inflight
                    for r in running.values()
                ):
                    nxt = self._dispatch_chunk(running)
            # nxt, if any, runs on the device while this one drains: the
            # drain's phases are host-only exactly when nothing is in flight
            inflight, self._inflight = self._inflight, nxt
            ready, self._pending_retire = self._pending_retire, []
            finished.extend(self._drain_chunk(inflight, defer_retire=True))
            for r in ready:
                # the chunk that could still write these slots has drained
                self._retire(r)
            if nxt is None:
                for r in self._pending_retire:
                    self._retire(r)
                self._pending_retire = []
            return finished
        running = self._running()
        if running:
            self._inflight = self._dispatch_chunk(running)
        return finished

    def _prefill_waiting(self, finished: List[Request]) -> None:
        """Admit and prefill the waiting queue from its head until a
        request is refused, one prompt ahead of the device: request i+1
        is admitted and dispatched BEFORE request i's results are read,
        so the host's work for i+1 runs under i's prefill and not before
        an idle chip. Nothing i+1's admission and dispatch need is a
        result of i, or waits for the device (a slot, pages, a table row,
        the prompt's counts, a key made on the host's backend), the
        pool threads i+1 behind i on the device, and a slot's key and
        sampling mirrors are written in its own finish half, before any
        upload. Depth one: i's first token waits for one successor's
        admission, never for the queue. Every prompt is finished before
        this returns; the pipeline is this loop's local. A request that
        ends on its first token frees its slot and pages one admission
        later than if it were fetched first."""
        unfetched = None
        while True:
            dispatched = None
            if self._waiting and self._try_admit(
                self._waiting[0], unfetched is not None
            ):
                dispatched = self._dispatch_prefill(
                    self._waiting.pop(0), unfetched is not None
                )
            if unfetched is not None:
                self._finish_prefill(unfetched, dispatched is not None)
                req = unfetched[0]
                if req.done:
                    self._retire(req)
                    finished.append(req)
            if dispatched is None:
                return
            unfetched = dispatched

    def _running(self) -> Dict[int, Request]:
        # mid-prefill slots (packed serving) are not decodable yet: their
        # budget mirror is 0, and the packed branch guarantees the chunk
        # program never dispatches while any slot is prefilling
        return {
            r.slot: r
            for r in self._slots
            if r is not None and not r.done and not r.prefilling
        }

    def _dispatch_chunk(self, running: Dict[int, Request]):
        """Dispatch one compiled decode chunk (async — jax returns
        futures); the matching _drain_chunk does the single host sync."""
        max_remaining = max(
            r.max_new_tokens - len(r.out_tokens) for r in running.values()
        )
        # At most two compiled chunk programs (T=decode_chunk and T=1):
        # compiles are expensive on TPU, and a serving engine at steady
        # state always has >= decode_chunk tokens of demand. The drain
        # tail of a batch run follows cfg.drain_tail (single steps, or
        # one full chunk with the surplus steps frozen in-program).
        if max_remaining >= self.cfg.decode_chunk or self._drain_tail_chunk:
            T = self.cfg.decode_chunk
        else:
            T = 1
        reupload = self._dirty or self._dev is None
        if self.lockstep is not None:
            self.lockstep.chunk(T, reupload)
        if reupload:
            self._upload_sched()
        elif self._rows_stale:
            # packed engines only: the mixed step advanced the small
            # per-slot mirrors host-side (and admissions/retires touched
            # the page table); the [b, vocab] counts stay device-exact
            self._upload_sched_rows()
        with tracing.phase("sched.chunk_dispatch", self.chunk_in_flight) as ph:
            ph.set(T=T, live_slots=len(running))
            self._count_passes(T)
            d = self._dev
            mtp = self.programs.mtp
            (
                toks_dev, lps_dev, avs_dev, ais_dev, lt, pos, budget,
                cache, counts_dev, skeys_dev, *drafted
            ) = self._chunk_fn(T)(
                self.params,
                d["lt"],
                d["pos"],
                d["budget"],
                self.pool.as_tuple(),
                d["pt"],
                d["temps"],
                d["topp"],
                d["counts"],
                d["pres"],
                d["freq"],
                d["skeys"],
                d["eos_on"],
                d["bias"],
                *([d["draft"]] if mtp else []),
            )
            self.pool.replace(cache)
            self._dev = {
                "lt": lt, "pos": pos, "budget": budget,
                "pt": d["pt"], "temps": d["temps"], "topp": d["topp"],
                "counts": counts_dev, "pres": d["pres"], "freq": d["freq"],
                "skeys": skeys_dev, "eos_on": d["eos_on"], "bias": d["bias"],
            }
            if mtp:
                # drafted: (tokens that count a step and slot, the drafts)
                self._dev["draft"] = drafted[1]
        return (
            toks_dev, lps_dev, avs_dev, ais_dev, skeys_dev, running, T,
            *drafted,
        )

    def _drain_chunk(self, inflight, defer_retire: bool = False):
        """Fetch one dispatched chunk's results (the single blocking host
        sync per chunk) and emit its tokens."""
        (
            toks_dev, lps_dev, avs_dev, ais_dev, skeys_dev, running, T,
            *drafted,
        ) = inflight
        # The key mirror rides the batched device_get: a dirty re-upload
        # must not rewind any slot's key stream to a pre-chunk state.
        # Pipelined: a later chunk's dispatch DONATES this chunk's skeys
        # output (is_deleted) — skip the stale sync; the later chunk's own
        # drain supplies the fresh mirror, and a re-upload is always
        # preceded by that drain (dirty state blocks pre-dispatch).
        overlapped = self.chunk_in_flight
        with tracing.phase("sched.chunk_fetch", overlapped):
            if skeys_dev.is_deleted():
                skeys_host = None
                toks, lps, avs, ais, *drafted = jax.device_get(
                    (toks_dev, lps_dev, avs_dev, ais_dev, *drafted)
                )
            else:
                toks, lps, avs, ais, skeys_host, *drafted = jax.device_get(
                    (toks_dev, lps_dev, avs_dev, ais_dev, skeys_dev, *drafted)
                )
        with tracing.phase("sched.emit", overlapped) as ph:
            emitted = self.total_tokens_emitted
            delivered = tracing.emit_deliveries()
            if drafted:
                self.mtp_steps += T
            finished = self._emit_chunk(
                toks, lps, avs, ais, skeys_host, running, defer_retire,
                *drafted,
            )
            ph.set(
                tokens=self.total_tokens_emitted - emitted,
                deliveries=tracing.emit_deliveries() - delivered,
                finished=len(finished),
            )
        return finished

    def _emit_chunk(
        self, toks, lps, avs, ais, skeys_host, running, defer_retire,
        ns=None, drafts=None,
    ) -> List[Request]:
        """The host half of a drained chunk: key mirror, one run of tokens
        a request (``_emit_run``, which writes the slot's small mirrors),
        the token counts of all runs in one call, retires, and LAST the
        streaming hooks, one call a request, back to back: the first of
        them wakes the server's loop, and a writer that shares the GIL
        should find this thread past its bookkeeping. Returns the requests
        that finished, in the order a walk step by step would finish
        them. A chunk that drafts (``ns`` [T, slots]: how many of a step's
        two tokens count; ``drafts`` [slots]) holds a step's tokens side by
        side: a slot's run is the tokens that count, in order."""
        if skeys_host is not None:
            # only the rows this chunk actually advanced: a request
            # admitted while the chunk was in flight had its key written
            # by prefill AFTER dispatch, and a wholesale copy would rewind
            # it to the pre-admission (zero) snapshot
            for slot in running:
                self._slot_keys[slot] = skeys_host[slot]
        if ns is not None:
            # [T, slots, 2] -> [T * 2, slots], a step's first token first;
            # ``keep`` marks the entries that count
            flat = lambda x: x.swapaxes(1, 2).reshape(  # noqa: E731
                -1, x.shape[1], *x.shape[3:]
            )
            keep = flat(np.arange(2) < ns[..., None])
            toks, lps, avs, ais = map(flat, (toks, lps, avs, ais))
        # [T, slots] -> one list of T a slot, converted once
        toks_l = toks.T.tolist()
        lps_l = lps.T.tolist()
        ended: List[Tuple[int, Request]] = []
        emitted: List[Request] = []
        taken = np.zeros(toks.shape[1], dtype=np.int64)
        #: rows the chunk program was traced with (the expert layers' form)
        rows = self.cfg.max_batch * (1 if ns is None else 2)
        for slot, req in running.items():
            # aborted between dispatch and drain: its tokens are frozen
            # repeats, and abort already handled the retire
            if req.done:
                continue
            run_toks, run_lps = toks_l[slot], lps_l[slot]
            kept = slice(None)
            if ns is not None:
                kept = np.nonzero(keep[:, slot])[0].tolist()
                run_toks = [run_toks[i] for i in kept]
                run_lps = [run_lps[i] for i in kept]
                self._drafts[slot] = drafts[slot]
                self.mtp_drafted += int(np.count_nonzero(ns[:, slot]))
                self.mtp_accepted += int(np.count_nonzero(ns[:, slot] == 2))
                self.mtp_emitted += len(kept)
                if not run_toks:
                    continue
            alts = None
            if req.want_top_logprobs:
                alts = [
                    list(zip(i, v))
                    for i, v in zip(
                        ais[kept, slot].tolist(), avs[kept, slot].tolist()
                    )
                ]
            first = req.pos
            n = self._emit_run(req, run_toks, run_lps, alts)
            # every token of the run was a step that wrote its position
            self._count_forward(first, n, rows)
            if ns is None:
                taken[slot] = n
            else:
                np.add.at(self._token_counts[slot], run_toks[:n], 1)
                n = kept[n - 1] + 1  # where in the chunk the run ended
            emitted.append(req)
            if req.done:
                ended.append((n, req))
        # host counts mirror the device copy the chunk program updates
        # (stop-stripped tokens stay counted on both sides): every run's
        # tokens, toks[:taken[slot], slot], in one call
        step, slot = np.nonzero(np.arange(toks.shape[0])[:, None] < taken)
        np.add.at(self._token_counts, (slot, toks[step, slot]), 1)
        ended.sort(key=lambda e: e[0])
        finished = [req for _, req in ended]
        for req in finished:
            if defer_retire:
                self._defer_retire(req)
            else:
                self._retire(req)
        for req in emitted:
            self._stream(req)
        return finished

    def _defer_retire(self, req: Request) -> None:
        """A finished request whose pages a still-in-flight chunk may yet
        write: freeze its slot on the next reupload and postpone the page
        free / prefix-cache registration until that chunk drains."""
        self._budgets[req.slot] = 0
        self._dirty = True
        self._pending_retire.append(req)

    def drain_inflight(self) -> None:
        """Complete any dispatched-but-unread decode chunk and flush
        deferred retires. Called before sleep/offload (the results would
        otherwise be lost with the device state). Finished requests are
        NOT returned — they are handed to the next step() call via the
        orphan list, so exactly one consumer (the service loop) resolves
        them."""
        if self._inflight is not None:
            inflight, self._inflight = self._inflight, None
            self._orphan_finished.extend(self._drain_chunk(inflight))
        for r in self._pending_retire:
            self._retire(r)
        self._pending_retire = []

    @property
    def chunk_in_flight(self) -> bool:
        """A dispatched decode chunk has not been fetched yet (pipelined
        decode): host work beside it does not hold the device back, which
        is what ``tracing.phase``'s ``overlapped`` is told."""
        return self._inflight is not None

    def has_work(self) -> bool:
        return (
            bool(self._waiting)
            or any(s is not None for s in self._slots)
            or self._inflight is not None
            or bool(self._orphan_finished)
        )

    # -- zero-drain park/resume (engine/parked.py) ---------------------------

    def parked_page_ids(self) -> List[int]:
        """Unique pool page ids a park would page out right now — the
        first ``ceil(pos / page_size)`` pages of every live mid-decode
        request, in order of first use. Shared prefix pages appear once.
        Also the byte basis of the cost oracle's park pricing: the park
        itself gathers exactly this list, so predicted and actual
        page-out bytes agree by construction."""
        out: List[int] = []
        seen: set = set()
        for req in self._slots:
            if req is None or req.done or req.prefilling:
                continue
            used = (
                PageAllocator.pages_needed(req.pos, self.cfg.page_size)
                if req.pos > 0
                else 0
            )
            for p in req.pages[:used]:
                if p not in seen:
                    seen.add(p)
                    out.append(p)
        return out

    def park_requests(self, bucket_bytes: "int | None" = None):
        """Preempt every live and queued request into a host-resident
        :class:`~.parked.ParkedRequests` bundle and drop the KV pool's
        device arrays (``kv_detached``): the engine is then empty — an
        actuation can sleep/swap it without aborting anything, and
        ``resume_parked`` re-seats the bundle bit-exact afterwards.

        Ordering is failure-safe: the KV page-out (fault point
        ``kvsave.d2h``) runs BEFORE any scheduler state is touched, so a
        failed page-out raises with the engine still serving and the
        caller falls back to today's abort path. Returns
        ``(bundle, finished)`` — ``finished`` are requests a pipelined
        drain completed during the quiesce (the caller resolves their
        futures; they were never preempted).

        Mid-prefill (packed) requests are demoted back to the waiting
        queue instead of carrying KV: prefill is a pure function of the
        prompt and no RNG split is consumed before its final segment, so
        re-running it on resume reproduces identical output."""
        refuse_slot_state(self._model_cfg, "a zero-drain park")
        from . import parked as parked_mod

        self.drain_inflight()
        live_reqs = [
            r for r in self._slots
            if r is not None and not r.done and not r.prefilling
        ]
        demote = [
            r for r in self._slots
            if r is not None and not r.done and r.prefilling
        ]
        page_ids = self.parked_page_ids()
        k_host = v_host = None
        kv_nbytes = 0
        pageout_s = 0.0
        if page_ids:
            # the faultable transfer, first: nothing below runs unless
            # every live page landed on host. Timed HERE, around the
            # gather alone: the drain/bookkeeping outside it must not
            # anchor the kvsave.d2h bandwidth EWMA low (the sleep.d2h
            # pure-window discipline)
            t0 = time.monotonic()
            k_host, v_host = parked_mod.gather_pages_d2h(
                self.pool, page_ids, bucket_bytes=bucket_bytes,
                span_name="swap.kv_pageout",
            )
            pageout_s = time.monotonic() - t0
            kv_nbytes = int(k_host.nbytes) + int(v_host.nbytes)
        finished = list(self._orphan_finished)
        self._orphan_finished = []
        bundle = parked_mod.ParkedRequests(
            page_ids=page_ids, k_host=k_host, v_host=v_host,
            kv_nbytes=kv_nbytes, pageout_s=pageout_s,
        )
        meta_nbytes = 0
        for r in live_reqs:
            used = PageAllocator.pages_needed(r.pos, self.cfg.page_size)
            pr = parked_mod.ParkedRequest(
                req=r,
                old_pages=list(r.pages[:used]),
                counts_row=np.array(self._token_counts[r.slot], copy=True),
                key_data=np.array(self._slot_keys[r.slot], copy=True),
            )
            meta_nbytes += pr.counts_row.nbytes + pr.key_data.nbytes
            bundle.live.append(pr)
        if self.prefix_cache is not None:
            # refcounts and the hash index die with the pool; resumed
            # pages re-acquire fresh references (the cache restarts cold)
            for r in live_reqs + demote:
                self.prefix_cache.release(r.pages)
            self.prefix_cache.clear()
        for r in demote:
            r.prefilling = False
            r.pos = 0
            r.cached_tokens = 0
            r.shared_pages = 0
            r.pages = []
            r.slot = -1
            r._prefix_hashes = ()
            if hasattr(r, "_blocked_state"):
                del r._blocked_state
            bundle.waiting.append(r)
        for r in live_reqs:
            r.slot = -1
            r.pages = []
        bundle.waiting.extend(self._waiting)
        bundle.nbytes = kv_nbytes + meta_nbytes
        # detach: wipe the scheduler wholesale (the pool and allocator
        # are rebuilt fresh by set_state/rebuild_kv_pool on restore)
        self._slots = [None] * self.cfg.max_batch
        self._waiting = []
        self._page_table[:, : self.cfg.pages_per_seq] = 0
        self._positions[:] = 0
        self._last_tokens[:] = 0
        self._temps[:] = 0.0
        self._topps[:] = 1.0
        self._pres[:] = 0.0
        self._freqs[:] = 0.0
        self._token_counts[:] = 0
        self._budgets[:] = 0
        self._slot_keys[:] = 0
        self._eos_on[:] = 1
        self._bias[:] = 0.0
        self._fresh_slots.clear()
        self._rows_stale = False
        self._dirty = True
        for leaf in self.pool.as_tuple():
            if leaf is not None:
                leaf.delete()
        self.pool.drop()
        self.kv_detached = True
        return bundle, finished

    def rebuild_kv_pool(self) -> None:
        """Fresh device KV pool + allocator after a zero-drain park
        dropped them (called by the sleeper's set_state when the restored
        state carries no "kv" subtree, and by rollback paths)."""
        self._create_pool()
        self.allocator = PageAllocator(self.cfg.num_pages)
        self.kv_detached = False

    def resume_parked(
        self, bundle, bucket_bytes: "int | None" = None
    ) -> Tuple[int, int]:
        """Re-seat a parked bundle into this (awake, empty-pool) engine:
        allocate pages, page the saved KV back in (fault point
        ``kvrestore.h2d``), rewrite page tables through the old->new page
        map (preserving prefix-page sharing between live requests), and
        restore every per-slot mirror — the next dispatch re-uploads the
        whole scheduler state (_dirty), so the resumed decode continues
        bit-exact mid-stream.

        Returns ``(live_resumed, kv_pagein_bytes)``. On a page-in
        failure everything is unwound — allocated pages freed, no slot
        seated, ``bundle.waiting`` re-queued (they carried no KV and lost
        nothing) — and :class:`~.parked.ParkedResumeFailed` is raised so
        the caller aborts the live requests with cause ``state_loss``;
        the engine stays healthy with an empty pool."""
        from . import parked as parked_mod

        if self.kv_detached:
            raise parked_mod.ParkedResumeFailed(
                "resume before the KV pool was rebuilt"
            )
        old2new: Dict[int, int] = {}
        seated: List[tuple] = []
        moved = 0
        try:
            for pr in bundle.live:
                r = pr.req
                need = PageAllocator.pages_needed(
                    len(r.prompt) + r.max_new_tokens, self.cfg.page_size
                )
                new_pages: List[int] = []
                fresh: List[int] = []  # allocated by THIS request
                fresh_old: List[int] = []  # ...and mapped into old2new
                try:
                    for j in range(need):
                        old = (
                            pr.old_pages[j]
                            if j < len(pr.old_pages)
                            else None
                        )
                        if old is not None and old in old2new:
                            new_pages.append(old2new[old])
                            continue
                        got = self._alloc_pages(1)[0]
                        fresh.append(got)
                        if old is not None:
                            old2new[old] = got
                            fresh_old.append(old)
                        new_pages.append(got)
                except BaseException:
                    # free this request's own partial allocation (pages
                    # reused from earlier requests stay theirs; fully
                    # seated requests are unwound by the outer handler)
                    self.allocator.free(fresh)
                    for old in fresh_old:
                        old2new.pop(old, None)
                    raise
                if self.prefix_cache is not None:
                    # one reference per referencing sequence, like
                    # _admit: retire's release then refcounts shared
                    # prefix pages correctly
                    self.prefix_cache.acquire(new_pages)
                seated.append((pr, new_pages))
            if bundle.page_ids:
                pairs = [
                    (i, old2new[p])
                    for i, p in enumerate(bundle.page_ids)
                    if p in old2new
                ]
                moved = parked_mod.scatter_pages_h2d(
                    self.pool, pairs, bundle.k_host, bundle.v_host,
                    bucket_bytes=bucket_bytes,
                    span_name="wake.kv_pagein",
                )
        except BaseException as e:
            for pr, new_pages in seated:
                if self.prefix_cache is not None:
                    self.allocator.free(
                        self.prefix_cache.release(new_pages)
                    )
                else:
                    self.allocator.free(new_pages)
            self._waiting.extend(bundle.waiting)
            self._dirty = True
            raise parked_mod.ParkedResumeFailed(
                f"{type(e).__name__}: {e}"
            ) from e
        # no failure past this point: seating is pure host bookkeeping
        for pr, new_pages in seated:
            r = pr.req
            slot = self._free_slot()
            assert slot is not None, "parked batch exceeded max_batch"
            r.slot = slot
            r.pages = new_pages
            r.shared_pages = 0
            r._prefix_hashes = ()
            self._slots[slot] = r
            row = np.zeros((self.cfg.pages_per_seq,), dtype=np.int32)
            row[: len(new_pages)] = new_pages
            self._page_table[slot, : row.size] = row
            self._positions[slot] = r.pos
            self._last_tokens[slot] = (
                r.out_tokens[-1] if r.out_tokens else 0
            )
            self._temps[slot] = r.temperature
            self._topps[slot] = r.top_p
            self._pres[slot] = r.presence_penalty
            self._freqs[slot] = r.frequency_penalty
            self._token_counts[slot] = pr.counts_row
            self._budgets[slot] = r.max_new_tokens - len(r.out_tokens)
            self._eos_on[slot] = 0 if r.ignore_eos else 1
            self._bias[slot] = 0.0
            for t, v in r.logit_bias.items():
                self._bias[slot, t] = v
            self._slot_keys[slot] = pr.key_data
        self._waiting = list(bundle.waiting) + self._waiting
        self._dirty = True
        return len(seated), moved

    def abort(self, seq_id: int, reason: str = "aborted") -> bool:
        """Abort one request (client disconnect): waiting requests are
        dropped, in-flight ones retired — their pages return to the pool and
        the slot frees this step instead of decoding to max_new_tokens."""
        for i, req in enumerate(self._waiting):
            if req.seq_id == seq_id:
                self._waiting.pop(i)
                req.done = True
                req.error = reason
                return True
        for req in self._slots:
            if req is not None and req.seq_id == seq_id:
                if req.done:
                    # finished on its own terms, retire merely deferred
                    # (pipelined); deferring again would double-free its
                    # pages — and the legitimate finish must stand
                    return False
                if self._inflight is not None:
                    # an in-flight chunk may still write this slot's pages
                    self._defer_retire(req)
                else:
                    self._retire(req)
                req.done = True
                req.error = reason
                return True
        return False

    def abort_all(self, reason: str) -> List[Request]:
        """Fail every waiting and in-flight request and reset the scheduler
        (slots, page tables, allocator, prefix cache). Used when continuity
        of generation cannot be preserved — e.g. a level-2 sleep discarded
        the KV cache, which also invalidates every cached prefix page."""
        # a dispatched chunk's results are irrelevant (everything aborts);
        # deferred-retire requests still occupy _slots, so the loop below
        # retires them with everyone else
        self._inflight = None
        self._pending_retire = []
        aborted = list(self._waiting)
        self._waiting.clear()
        for req in list(self._slots):
            if req is not None:
                if not req.done:
                    # deferred-retire requests finished on their own terms;
                    # only genuinely in-flight ones get the abort error
                    aborted.append(req)
                self._retire(req)
        for req in aborted:
            req.done = True
            req.error = reason
        if self.prefix_cache is not None:
            # the KV content backing the index is gone: matching a stale
            # chain would silently attend over garbage pages
            self.allocator.free(self.prefix_cache.clear())
        return aborted

    # -- convenience --------------------------------------------------------

    def generate(
        self,
        prompts: Seq[Seq[int]],
        max_new_tokens: int = 16,
        temperature: float = 0.0,
    ) -> List[List[int]]:
        ids = [
            self.add_request(p, max_new_tokens, temperature) for p in prompts
        ]
        results: Dict[int, List[int]] = {}
        while self.has_work():
            for req in self.step():
                results[req.seq_id] = req.out_tokens
        return [results[i] for i in ids]
