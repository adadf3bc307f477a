"""Engine HTTP server: OpenAI-style completions + the sleep/wake admin API.

This is the process the launcher forks (the reference forks `vllm serve` with
VLLM_SERVER_DEV_MODE admin endpoints; here it's our JAX engine). The admin
contract is engine-agnostic and matches what the dual-pods controller speaks
(inference-server.go:1497,1712,1984):

  GET  /health       200 once serving
  GET  /is_sleeping  {"is_sleeping": bool}
  POST /sleep?level=1|2
  POST /wake_up

Inference:
  POST /v1/completions       {"prompt": str | [int], "max_tokens",
                              "temperature", "top_p", "stop",
                              "logprobs", "stream"}
  POST /v1/chat/completions  {"messages": [{role, content}...], ...}
  GET  /v1/models

Both generation endpoints stream OpenAI-style SSE (`data: {json}` per token,
`data: [DONE]` terminator) when `"stream": true`.

The engine loop runs on a dedicated thread (device steps block); HTTP
handlers enqueue requests and await futures. Sleep acquires the step lock, so
it happens on a step boundary with no request in flight on device.
"""

from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import contextlib
import json
import logging
import os
import shlex
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from aiohttp import web
from prometheus_client import Counter, Gauge, Histogram

from ..models import llama
from ..models.moe import MoeConfig
from ..models.exaone_moe import ExaoneMoeConfig
from ..models.nemotron_h import NemotronHConfig
from ..models.olmo_hybrid import OlmoHybridConfig
from ..models.kimi_linear import KimiLinearConfig
from ..models.smallthinker import SmallThinkerConfig
from ..utils import faults, tracing
from .engine import (
    EngineConfig,
    InferenceEngine,
    deliver_tokens,
    resolve_attention_impl,
)
from .model_pool import HostModelPool
from .sleep import (
    SwapRolledBack,
    SwapRollbackFailed,
    attach_sleep,
    swap_states,
)

logger = logging.getLogger(__name__)

#: Scheduling pressure: waiting + in-flight requests. The HPA's per-pod
#: scaling signal (deploy/hpa/hpa.yaml); labeled by model because two
#: engine instances can share one process in tests.
ENGINE_QUEUE_DEPTH = Gauge(
    "fma_engine_queue_depth",
    "Requests waiting or in flight in this engine",
    ["model"],
)

# Serving observability (the vLLM-equivalent engine metrics an operator
# expects on the engine's /metrics; the reference serves vLLM's):
ENGINE_TTFT = Histogram(
    "fma_engine_time_to_first_token_seconds",
    "Submit to first emitted token",
    ["model"],
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30),
)
ENGINE_E2E_LATENCY = Histogram(
    "fma_engine_request_seconds",
    "Submit to request completion",
    ["model"],
    buckets=(0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60, 120),
)
ENGINE_PROMPT_TOKENS = Counter(
    "fma_engine_prompt_tokens_total", "Prompt tokens processed", ["model"]
)
ENGINE_GENERATED_TOKENS = Counter(
    "fma_engine_generation_tokens_total", "Tokens generated", ["model"]
)
ENGINE_ABORTS = Counter(
    "fma_engine_aborted_requests_total",
    "Requests aborted, by cause: client (disconnect), swap (actuation "
    "preempted queued/in-flight work), state_loss (level-2 wake)",
    ["model", "reason"],
)
# Zero-drain actuation (docs/perf.md "Zero-drain actuation"): instead of
# aborting, --zero-drain parks the victim model's live requests (KV pages
# paged out beside the slept weights) and resumes them bit-exact after the
# wake/swap-back. Every preempted request eventually resolves to exactly
# one outcome; the byte counter is the parked-KV transfer volume.
ENGINE_PREEMPTED = Counter(
    "fma_engine_preempted_requests_total",
    "Requests preempted by a zero-drain actuation, by final outcome "
    "(resumed = re-seated and continued; aborted = parked state lost — "
    "KV restore failure, parked-model eviction, or client disconnect "
    "while parked; migrated = handed off to a sibling instance and "
    "continued there)",
    ["model", "outcome"],  # outcome: resumed | aborted | migrated
)
ENGINE_KV_PAGEOUT = Counter(
    "fma_engine_kv_pageout_bytes_total",
    "Parked-KV bytes moved by zero-drain preempt/resume, by direction "
    "(d2h = page-out at park, h2d = page-in at resume)",
    ["dir"],
)
# Live request migration (docs/operations.md "Draining a node without
# dropping streams"): a zero-drain parked bundle handed to a sibling
# instance over the wire, resumed mid-decode on the destination. Source
# outcomes: committed (fence spent, results proxied) | resumed_local
# (export/import failed, streams continued at home) | state_loss (the
# double-fault degradation). Destination outcomes: imported | rolled_back.
ENGINE_MIGRATIONS = Counter(
    "fma_engine_migrations_total",
    "Live request migrations, by role (source|destination) and terminal "
    "outcome (committed | resumed_local | state_loss | imported | "
    "rolled_back)",
    ["role", "outcome"],
)
ENGINE_MIGRATE_BYTES = Counter(
    "fma_engine_migrate_bytes_total",
    "Parked-bundle KV bytes moved by live request migration, by "
    "direction (export = serialized to the wire on the source, import = "
    "paged into the destination pool)",
    ["dir"],
)
ENGINE_KV_USAGE = Gauge(
    "fma_engine_kv_cache_usage_ratio",
    "Fraction of KV pages in use",
    ["model"],
)
ENGINE_PREFIX_HIT_TOKENS = Gauge(
    "fma_engine_prefix_cache_hit_tokens",
    "Prompt tokens served from the prefix cache instead of prefill",
    ["model"],
)
ENGINE_SPEC_PROPOSED = Gauge(
    "fma_engine_spec_proposed_tokens",
    "Tokens proposed by n-gram speculative decoding",
    ["model"],
)
ENGINE_SPEC_ACCEPTED = Gauge(
    "fma_engine_spec_accepted_tokens",
    "Proposed tokens accepted by the verify forward",
    ["model"],
)
ENGINE_MTP_DRAFTED = Gauge(
    "fma_engine_mtp_drafted_tokens_total",
    "Drafts of the model's own prediction module that a verify step ran "
    "(--speculative-mtp): one a live slot a decode step",
    ["model"],
)
ENGINE_MTP_ACCEPTED = Gauge(
    "fma_engine_mtp_accepted_tokens_total",
    "Drafts the verify step accepted: steps that emitted two tokens",
    ["model"],
)

# SLO / goodput telemetry (docs/perf.md "Fleet benchmarking and goodput"):
# the request-lifecycle observables the multi-model scheduler (ROADMAP
# item 1) optimizes and a load generator reads from `/v1/stats`.
# Queue wait separates "sat behind other work / an actuation" from "the
# prefill itself was slow" inside the existing TTFT histogram.
ENGINE_QUEUE_WAIT = Histogram(
    "fma_engine_queue_wait_seconds",
    "Submit to first scheduled (queue time; prefill excluded)",
    ["model"],
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30),
)
ENGINE_SLO_REQUESTS = Counter(
    "fma_engine_slo_requests_total",
    "Finished requests judged against a configured SLO target "
    "(--slo-ttft-ms / --slo-tpot-ms; one observation per enabled slo)",
    ["model", "slo", "outcome"],  # slo: ttft|tpot, outcome: met|violated
)
ENGINE_GOODPUT_TOKENS = Counter(
    "fma_engine_goodput_tokens_total",
    "Generated tokens from requests that met every configured SLO "
    "(equals generation_tokens_total when no SLO target is set)",
    ["model"],
)
ENGINE_ARRIVAL_RATE = Gauge(
    "fma_engine_request_arrival_rate",
    "EWMA of request arrivals (requests/s) for the resident model — the "
    "demand signal a multi-model scheduler consumes",
    ["model"],
)

# Model hot-swap observability (docs/engine.md "Model hot-swap"): the swap
# is the actuation hot path, so its latency, how much of it overlapped, and
# the transfer window it held are all first-class operator signals.
ENGINE_SWAP_SECONDS = Histogram(
    "fma_engine_swap_seconds",
    "Model hot-swap wall time (labeled by the incoming model)",
    ["model"],
    buckets=(0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60),
)
ENGINE_SWAPS = Counter(
    "fma_engine_swaps_total",
    "Completed model hot-swaps by source of the incoming state",
    ["model", "source"],  # source: pool | cold
)
ENGINE_SWAP_OVERLAP_FRAC = Gauge(
    "fma_engine_swap_overlap_fraction",
    "Fraction of the last swap spent with both DMA directions in flight",
    ["model"],
)
ENGINE_SWAP_INFLIGHT_BYTES = Gauge(
    "fma_engine_swap_peak_bytes_in_flight",
    "Peak transfer bytes in flight during the last swap",
    ["model"],
)
ENGINE_POOL_BYTES = Gauge(
    "fma_engine_model_pool_bytes",
    "Pinned-host bytes held by pooled (slept) models",
)
ENGINE_POOL_MODELS = Gauge(
    "fma_engine_model_pool_models",
    "Models resident in the host model pool",
)
ENGINE_POOL_HITS = Counter(
    "fma_engine_model_pool_hits",
    "Swap-ins served from the host model pool (no checkpoint re-read)",
)
ENGINE_POOL_EVICTIONS = Counter(
    "fma_engine_model_pool_evictions",
    "Pooled models evicted (budget pressure or device release)",
)

# Tiered, content-addressed pool (docs/perf.md "Tiered weight cache and
# delta swap"): per-tier residency, how many host bytes dedup across
# sibling fine-tune variants is saving right now, tier traffic, and how
# much of the last swap crossed the device boundary vs was content-matched
# away.
ENGINE_POOL_TIER_BYTES = Gauge(
    "fma_engine_model_pool_tier_bytes",
    "Bytes resident per model-pool tier (host chunks / disk spill)",
    ["tier"],  # host | disk
)
ENGINE_POOL_TIER_CHUNKS = Gauge(
    "fma_engine_model_pool_tier_chunks",
    "Content-addressed chunks resident per model-pool tier",
    ["tier"],
)
ENGINE_POOL_DEDUP_SAVED = Gauge(
    "fma_engine_model_pool_dedup_saved_bytes",
    "Host bytes saved by content-addressed dedup across pooled models",
)
ENGINE_POOL_TIER_EVENTS = Counter(
    "fma_engine_model_pool_tier_events_total",
    "Chunk-store traffic by event",
    ["event"],  # dedup_hit | host_hit | disk_spill | disk_hit |
    #             disk_eviction | verify_failure | miss
)
ENGINE_SWAP_DELTA_BYTES = Gauge(
    "fma_engine_swap_delta_bytes",
    "Last swap's bytes over the device boundary by kind",
    ["model", "kind"],  # kind: moved | deduped
)

# Compressed actuation (docs/perf.md "Compressed actuation"): cumulative
# wire bytes per transfer mode and direction across every sleep / wake /
# swap edge — the signal for "what is --sleep-quant actually saving".
# A Gauge used as a monotonic accumulator so the exposition name matches
# the documented fma_engine_actuation_bytes{mode,dir} exactly.
ENGINE_ACTUATION_BYTES = Gauge(
    "fma_engine_actuation_bytes",
    "Cumulative actuation transfer bytes by mode and direction",
    ["mode", "dir"],  # mode: off | int8 | fp8; dir: d2h | h2d
)

# Actuation cost oracle + decision flight recorder (docs/operations.md
# "Pricing an actuation"; utils/costs.py): durations next to the byte
# counter above — bytes without seconds can't validate the oracle from
# Prometheus alone — plus the last prediction per kind and how wrong it
# was. The scheduler-brain's cost telemetry (ROADMAP item 1).
ENGINE_ACTUATION_SECONDS = Histogram(
    "fma_engine_actuation_seconds",
    "Actuation wall seconds by kind and phase (phase=d2h/h2d are the "
    "transfer windows; total is the whole verb incl. overlap/commit)",
    ["kind", "phase"],  # kind: swap | sleep | wake; phase: d2h | h2d | total
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60, 120),
)
ENGINE_PREDICTED_BYTES = Gauge(
    "fma_engine_actuation_predicted_bytes",
    "Last actuation's oracle-predicted wire bytes, by kind (compare "
    "against fma_engine_actuation_bytes increments: byte prediction is "
    "deterministic from digests/shapes, so any drift is a bug signal)",
    ["kind"],
)
ENGINE_COST_ERROR = Gauge(
    "fma_engine_cost_prediction_error_ratio",
    "Signed relative error (predicted-actual)/actual of the last "
    "actuation's predicted seconds, by kind — the oracle's live "
    "accuracy score (only set when the prediction used measured "
    "bandwidth)",
    ["kind"],
)

# Self-healing observability (docs/operations.md "Self-healing and fault
# drills"): every recovery edge — a swap failure rolled back in-process, or
# a rollback that itself failed and flipped /health — is counted, so an
# operator can tell "the failure path fired and healed" apart from silence.
ENGINE_RECOVERIES = Counter(
    "fma_engine_recoveries_total",
    "Recovery attempts by path and outcome",
    ["path", "outcome"],  # path: swap | swap_cold; outcome: rolled_back |
    #                       rollback_failed
)

# Cold-start observability (docs/perf.md "Cold-start tuning"): the pipelined
# loader's phase breakdown for the last cold build, and background-prefetch
# outcomes. `phase` is read (disk -> staged host buffers, wall window),
# convert (cumulative casted-copy time inside staging), h2d (first transfer
# issued -> last landed) or total.
ENGINE_COLDLOAD_PHASE_SECONDS = Gauge(
    "fma_engine_coldload_phase_seconds",
    "Last cold weight-load phase timing",
    ["model", "phase"],  # phase: read | convert | h2d | total
)
ENGINE_COLDLOAD_OVERLAP_FRAC = Gauge(
    "fma_engine_coldload_overlap_fraction",
    "Fraction of the last cold load spent with disk read and H2D in flight",
    ["model"],
)
ENGINE_PREFETCHES = Counter(
    "fma_engine_prefetch_total",
    "Background checkpoint prefetches by outcome",
    ["outcome"],  # completed | aborted | failed | rejected
)
ENGINE_PREFETCH_BYTES = Gauge(
    "fma_engine_prefetch_staged_bytes",
    "Host bytes staged by the last completed prefetch",
)

# AOT warmup + executable pool (docs/perf.md "Warmup and the executable
# pool"): first-touch compiles were the tail that wagged TTFT after the
# streaming loaders fixed weight movement — these say whether the compile
# work is riding under transfers (warmup seconds per program) and whether
# rebuilds are reusing executables instead of recompiling (pool traffic).
ENGINE_WARMUP_SECONDS = Gauge(
    "fma_engine_warmup_seconds",
    "AOT warmup compile seconds by program (last warmup)",
    ["program"],
)
ENGINE_EXEC_POOL_HITS = Counter(
    "fma_engine_exec_pool_hits_total",
    "Executable-pool lookups served without compiling",
)
ENGINE_EXEC_POOL_MISSES = Counter(
    "fma_engine_exec_pool_misses_total",
    "Executable-pool lookups that had to compile",
)
ENGINE_EXEC_POOL_EVICTIONS = Counter(
    "fma_engine_exec_pool_evictions_total",
    "Executables evicted from the pool (budget pressure or device release)",
)
ENGINE_EXEC_POOL_BYTES = Gauge(
    "fma_engine_exec_pool_bytes",
    "Estimated host bytes held by pooled executables",
)
ENGINE_EXEC_POOL_ENTRIES = Gauge(
    "fma_engine_exec_pool_entries",
    "Executables resident in the pool",
)

# Mixed-batch (token-packed) serving observability (docs/metrics.md): how
# full the decode batch runs, how densely the packed buffer is used, and
# how much activation padding each dispatch path burns — the occupancy/
# queue signals the multi-model scheduler (ROADMAP item 1) consumes.
ENGINE_SLOT_OCCUPANCY = Gauge(
    "fma_engine_decode_slot_occupancy",
    "Fraction of decode slots occupied by running requests",
    ["model"],
)
ENGINE_PACKED_TOKENS = Histogram(
    "fma_engine_packed_tokens_per_step",
    "Valid (non-padding) tokens packed into each mixed-batch step",
    ["model"],
    buckets=(8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
)
ENGINE_PAD_WASTE_BYTES = Counter(
    "fma_engine_prefill_pad_waste_bytes_total",
    "Activation bytes computed for padding tokens, by dispatch path "
    "(bucketed = power-of-two prefill bucket padding; packed = invalid "
    "rows of the mixed [token_budget] buffer)",
    ["model", "path"],
)
ENGINE_STEP_H2D_BYTES = Counter(
    "fma_engine_step_h2d_bytes_total",
    "Host->device scheduler/dispatch bytes moved by engine steps, by "
    "serving path (packed = mixed-program row inputs + a packed "
    "engine's scheduler uploads, which are O(rows) per step at steady "
    "state — the [max_batch, vocab] mirrors re-upload only on dirty "
    "edges; bucketed = prefill/suffix/spec dispatch inputs + a "
    "bucketed engine's scheduler uploads)",
    ["model", "path"],
)

MODEL_CONFIGS = {
    "tiny": llama.LlamaConfig.tiny,
    "llama3-8b": llama.LlamaConfig.llama3_8b,
    "llama3-70b": llama.LlamaConfig.llama3_70b,
    "tiny-moe": MoeConfig.tiny_moe,
    "mixtral-8x7b": MoeConfig.mixtral_8x7b,
    "tiny-gemma": llama.LlamaConfig.tiny_gemma,
    "gemma3-4b": llama.LlamaConfig.gemma3_4b,
    "qwen2-7b": lambda: llama.LlamaConfig(
        vocab_size=152064,
        hidden_size=3584,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=18944,
        rope_theta=1e6,
        max_seq_len=32768,
    ),
    "tinyllama-1.1b": lambda: llama.LlamaConfig(
        vocab_size=32000,
        hidden_size=2048,
        num_layers=22,
        num_heads=32,
        num_kv_heads=4,
        head_dim=64,
        intermediate_size=5632,
        rope_theta=10000.0,
        max_seq_len=2048,
    ),
    "bench-1b": lambda: llama.LlamaConfig(
        vocab_size=32000,
        hidden_size=2048,
        num_layers=24,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=5632,
        rope_theta=10000.0,
        max_seq_len=2048,
    ),
    "tiny-smallthinker": SmallThinkerConfig.tiny_smallthinker,
    "smallthinker-21b-a3b": SmallThinkerConfig.smallthinker_21b_a3b,
    "tiny-ouro": llama.LlamaConfig.tiny_ouro,
    "ouro-2.6b": llama.LlamaConfig.ouro_2_6b,
    "tiny-olmo-hybrid": OlmoHybridConfig.tiny_olmo_hybrid,
    "olmo-hybrid-7b": OlmoHybridConfig.olmo_hybrid_7b,
    "tiny-kimi-linear": KimiLinearConfig.tiny_kimi_linear,
    "kimi-linear-48b-a3b": KimiLinearConfig.kimi_linear_48b_a3b,
    "tiny-nemotron-h": NemotronHConfig.tiny_nemotron_h,
    "nemotron-3-super-120b-a12b": NemotronHConfig.nemotron_3_super_120b_a12b,
    "tiny-exaone-moe": ExaoneMoeConfig.tiny_exaone_moe,
    "k-exaone-236b-a23b": ExaoneMoeConfig.k_exaone_236b_a23b,
}


def make_arg_parser() -> argparse.ArgumentParser:
    """The engine's CLI (the `options` string of an instance config is parsed
    with exactly this parser, mirroring how the reference launcher reuses
    vLLM's own parser, launcher.py:871-883)."""
    p = argparse.ArgumentParser(prog="fma-engine", add_help=False)
    p.add_argument("--model", default="tiny", help="model name or config key")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--max-model-len", type=int, default=0)
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eos-token-id", type=int, default=-1)
    p.add_argument(
        "--attention-impl",
        default="auto",
        choices=["auto", "reference", "grouped", "pallas"],
        help="decode attention implementation (auto = pallas on TPU, "
        "grouped XLA elsewhere)",
    )
    p.add_argument(
        "--quantization",
        default="",
        choices=["", "int8"],
        help="weight-only quantization (int8 = W8A16 per-output-channel; "
        "halves decode's HBM weight reads)",
    )
    p.add_argument(
        "--prefix-caching",
        default="auto",
        choices=["auto", "on", "off"],
        help="automatic prefix caching: page-aligned KV reuse across "
        "requests sharing a prompt prefix (auto = on for every model "
        "whose KV state the cache can hold, off for a model with "
        "sliding-window layers, which an explicit on refuses by name)",
    )
    p.add_argument(
        "--decode-chunk",
        type=int,
        default=0,
        help="max decode steps fused into one compiled dispatch "
        "(0 = auto: 32 on TPU where per-dispatch latency dominates, "
        "8 elsewhere; docs/perf.md)",
    )
    p.add_argument(
        "--pipeline-decode",
        choices=["on", "off"],
        default="off",
        help="double-buffer decode chunks: dispatch chunk k+1 before "
        "reading chunk k (overlaps device compute with host fetch+emit; "
        "token delivery lags one chunk; ignored in gangs)",
    )
    p.add_argument(
        "--drain-tail",
        choices=["auto", "single", "chunk"],
        default="auto",
        help="batch drain tail: single T=1 steps, or one full chunk with "
        "surplus steps frozen in-program (saves up to chunk-1 dispatch "
        "round trips; auto = chunk on TPU, single elsewhere)",
    )
    p.add_argument(
        "--max-prefill-tokens",
        type=int,
        default=0,
        help="chunked prefill: segment prompts longer than this (bounds "
        "prefill memory and compile buckets); 0 = off",
    )
    p.add_argument(
        "--packed-serving",
        choices=["on", "off"],
        default="off",
        help="token-packed mixed-batch serving (docs/perf.md): whenever "
        "prefill work is pending, one compiled program processes a flat "
        "[token-budget] buffer packing prefill segments AND a decode "
        "step per running sequence — concurrent prompts neither "
        "serialize nor stall decode, and the per-bucket prefill "
        "programs leave the warmup plan. off (default) preserves the "
        "bucketed path byte-for-byte. Composes with sharded meshes "
        "(--tensor-parallel-size); incompatible with --pipeline-decode "
        "and multi-host gangs",
    )
    p.add_argument(
        "--token-budget",
        type=int,
        default=0,
        help="row capacity of the packed mixed-batch buffer "
        "(--packed-serving): bounds per-step prefill work like "
        "--max-prefill-tokens bounds segments. 0 = auto (256, floored "
        "so every decode slot plus one prefill block always fits)",
    )
    p.add_argument(
        "--speculative-ngram",
        type=int,
        default=0,
        help="n-gram (prompt-lookup) speculative decoding: verify up to N "
        "proposed tokens per forward on the single-sequence greedy path; "
        "0 = off",
    )
    p.add_argument(
        "--speculative-mtp",
        type=int,
        default=0,
        choices=[0, 1],
        help="self-speculative decoding with the model's own "
        "multi-token-prediction module, inside the decode chunk for every "
        "live slot: a step verifies the last token and the module's draft "
        "of the next (two positions a slot) and emits one token or two. "
        "1 = on, for a model that has a module (k-exaone-236b-a23b); 0 "
        "(default) serves the main path alone. Incompatible with "
        "--packed-serving and multi-host gangs",
    )
    p.add_argument(
        "--logprobs-topk",
        type=int,
        default=5,
        help="top-k alternative logprobs computed per token inside the "
        "compiled programs (OpenAI logprobs/top_logprobs; 0 disables)",
    )
    p.add_argument(
        "--slo-ttft-ms",
        type=float,
        default=0.0,
        help="TTFT SLO target in milliseconds (submit -> first token). "
        "Finished requests are judged against it "
        "(fma_engine_slo_requests_total{slo=ttft}) and only SLO-met "
        "requests count toward fma_engine_goodput_tokens_total "
        "(docs/perf.md 'Fleet benchmarking and goodput'); 0 disables",
    )
    p.add_argument(
        "--slo-tpot-ms",
        type=float,
        default=0.0,
        help="time-per-output-token SLO target in milliseconds (mean "
        "inter-token time after the first token); judged per finished "
        "request like --slo-ttft-ms; 0 disables",
    )
    p.add_argument(
        "--trace-requests",
        type=float,
        default=0.0,
        help="head-sampling fraction [0, 1] for per-request lifecycle "
        "traces (request.* span family, docs/tracing.md): each sampled "
        "request's queue/prefill/decode/preempt/migrate legs are "
        "retained in a dedicated trace ring served by GET /v1/traces. "
        "Independent of the fraction, SLO-violated, aborted, and "
        "migrated requests always keep their spans (tail-keep). "
        "0 (default) disables per-request tracing entirely — the "
        "serving hot path stays byte-identical",
    )
    p.add_argument(
        "--arrival-ewma-tau-s",
        type=float,
        default=30.0,
        help="time constant (seconds) of the request arrival-rate EWMA "
        "(fma_engine_request_arrival_rate): the demand signal's memory — "
        "shorter reacts faster to bursts, longer smooths them",
    )
    p.add_argument(
        "--zero-drain",
        choices=["on", "off"],
        default="off",
        help="preempt, page out, and resume live requests across model "
        "hot-swaps and level-1 sleeps instead of aborting them "
        "(docs/perf.md 'Zero-drain actuation'): the victim model's live "
        "KV pages are paged to host beside its slept weights "
        "(byte-counted against --model-pool-mib) and the streams resume "
        "mid-decode bit-exact on wake/swap-back. off (default) keeps "
        "today's abort path byte-for-byte. Multi-host gangs are "
        "rejected; level-2 and device-releasing sleeps keep their "
        "existing semantics",
    )
    p.add_argument(
        "--sleep-release-devices",
        default="auto",
        choices=["auto", "always", "never"],
        help="tear down the TPU client on sleep so other instances can use "
        "the chip (auto = on for TPU, off elsewhere)",
    )
    p.add_argument(
        "--model-pool-mib",
        type=int,
        default=4096,
        help="pinned-host byte budget (MiB) for the slept-model pool "
        "backing POST /v1/swap: models swapped out stay host-resident up "
        "to this budget so swapping back re-reads no checkpoint; 0 "
        "disables pooling (every swap-in is a cold build)",
    )
    p.add_argument(
        "--pool-disk-dir",
        default="",
        help="local-disk spill tier below the host model pool: weight "
        "chunks whose last pooled reference is evicted spill here "
        "(atomic rename, content-verified reload), so a swap back to an "
        "evicted model rebuilds from local disk instead of re-reading "
        "its checkpoint. Defaults to FMA_POOL_SPILL_DIR; empty disables "
        "the tier",
    )
    p.add_argument(
        "--pool-disk-mib",
        type=int,
        default=4096,
        help="byte budget (MiB) for the model pool's disk spill tier "
        "(LRU beyond it); 0 disables the tier",
    )
    p.add_argument(
        "--content-hash",
        default="on",
        choices=["on", "off"],
        help="content-address pooled weights (sha256 per leaf, computed "
        "once at load): dedupes sibling fine-tune variants in the host "
        "pool and lets hot-swaps move only the delta between models "
        "sharing tensors. Sharded single-process meshes participate "
        "with mesh-qualified digests (content hash + mesh shape + "
        "per-leaf sharding spec); ignored (off) for multi-host gangs "
        "and --quantization engines",
    )
    p.add_argument(
        "--sleep-quant",
        default="off",
        choices=["off", "int8", "fp8"],
        help="compressed actuation transfers (docs/perf.md): level-1 "
        "sleep offloads eligible weight stacks as int8 (per-channel "
        "scales) or fp8 (e4m3), only the payload crosses PCIe, and wake "
        "dequantizes on device — ~2x models per GiB of host pool and "
        "~half the transfer bytes per actuation. OPT-IN AND LOSSY-ONCE: "
        "the first quantized offload rounds the weights; every later "
        "cycle reproduces the same bits. off (default) keeps every "
        "sleep/wake/swap bit-exact. Composes with single-process "
        "--tensor-parallel-size meshes (shard-local quant/dequant on "
        "device); multi-host gangs are rejected",
    )
    p.add_argument(
        "--sleep-quant-hot-head",
        default="on",
        choices=["on", "off"],
        help="keep the 'hot head' (embeddings + final norm + lm_head) at "
        "full precision under --sleep-quant (the numerics-conservative "
        "default); off also quantizes embed/lm_head for maximum byte "
        "savings",
    )
    p.add_argument(
        "--swap-bucket-mib",
        type=int,
        default=256,
        help="transfer bucket size (MiB) for chunked sleep/wake and "
        "overlapped hot-swap: bounds peak extra HBM and the in-flight "
        "DMA window to ~one bucket per direction",
    )
    p.add_argument(
        "--exec-pool-mib",
        type=int,
        default=256,
        help="host byte budget (MiB) for the AOT executable pool "
        "(engine/exec_pool.py): compiled prefill/suffix/decode programs "
        "are pooled across swaps keyed by (config hash, mesh, dtype, "
        "bucket), so a rebuild of a previously-seen model recompiles "
        "nothing; 0 disables pooling (warmed executables still install "
        "into the engine being built)",
    )
    p.add_argument(
        "--warmup-buckets",
        default="",
        help="comma-separated prefill token buckets to AOT-precompile "
        "concurrently with swap/prefetch weight transfers (rounded up to "
        "the engine's power-of-two buckets; also warms the suffix-prefill "
        "and decode-chunk programs). Empty disables warmup — first-touch "
        "jit compile, the pre-existing behavior (docs/perf.md)",
    )
    p.add_argument(
        "--load-workers",
        type=int,
        default=0,
        help="parallel shard readers for cold HF weight loads "
        "(0 = auto: min(8, cpu count)); shard reads and dtype casts "
        "release the GIL, so readers genuinely overlap (docs/perf.md "
        "Cold-start tuning)",
    )
    p.add_argument(
        "--load-inflight-mib",
        type=int,
        default=512,
        help="bytes-in-flight bound (MiB) for the streaming cold loader's "
        "host->device transfers: buffers stream to HBM as they complete, "
        "double-buffered in ~half-this-size buckets",
    )
    p.add_argument(
        "--prefetch-mib-s",
        type=int,
        default=0,
        help="I/O throttle (MiB/s) for background checkpoint prefetch "
        "(POST /v1/prefetch) so staging the predicted next model never "
        "starves serving traffic; 0 = unthrottled",
    )
    p.add_argument(
        "--faults",
        default="",
        help="arm fault-injection points at startup (utils/faults.py), "
        'e.g. "swap.h2d=fail:1,coldload.read=delay:0.25" — the '
        "deterministic failure-drill knob; also armable via FMA_FAULTS "
        "env and POST /v1/faults",
    )
    p.add_argument(
        "--tokenizer",
        default="",
        help="HF tokenizer directory (text prompts, chat templates, stop "
        "strings, response text). Defaults to the hf: model directory when "
        "it ships tokenizer files; otherwise a byte-level fallback",
    )
    p.add_argument(
        "--checkpoint-dir",
        default="",
        help="load weights from this Orbax checkpoint (and reload from it "
        "on level-2 wake) instead of random init",
    )
    # Multi-host slice coordination (parallel/multihost.py): N engine
    # processes — one per host — form one jax.distributed job. Defaults
    # come from the FMA_NUM_PROCESSES / FMA_PROCESS_ID /
    # FMA_COORDINATOR_ADDRESS env the gang coordinator ships.
    p.add_argument("--num-processes", type=int, default=0)
    p.add_argument("--process-id", type=int, default=-1)
    p.add_argument("--coordinator-address", default="")
    return p


def resolve_distributed(args: argparse.Namespace) -> Optional[Dict[str, Any]]:
    """CLI flags > gang env > single-process default. Returns kwargs for
    jax.distributed.initialize, or None when single-process."""
    num = args.num_processes or int(os.environ.get("FMA_NUM_PROCESSES", "0") or 0)
    if num <= 1:
        return None
    pid = (
        args.process_id
        if args.process_id >= 0
        else int(os.environ.get("FMA_PROCESS_ID", "-1"))
    )
    addr = args.coordinator_address or os.environ.get(
        "FMA_COORDINATOR_ADDRESS", ""
    )
    if pid < 0 or pid >= num or not addr:
        raise ValueError(
            f"multi-host engine needs process-id in [0,{num}) and a "
            f"coordinator address (got id={pid}, addr={addr!r})"
        )
    return {
        "coordinator_address": addr,
        "num_processes": num,
        "process_id": pid,
    }


def validate_parsed_args(args: argparse.Namespace) -> None:
    if args.model.startswith("hf:"):
        # Hugging Face model directory (models/hf.py). Existence is checked
        # at engine start, not parse time: the controller validates options
        # strings on hosts that don't mount the model volume.
        if not args.model[3:]:
            raise ValueError("--model hf: needs a directory path")
    elif args.model not in MODEL_CONFIGS:
        raise ValueError(
            f"unknown model {args.model!r}; known: {sorted(MODEL_CONFIGS)} "
            "or hf:<model-dir>"
        )
    if args.tensor_parallel_size < 1:
        raise ValueError("--tensor-parallel-size must be >= 1")
    if args.decode_chunk < 0:
        raise ValueError("--decode-chunk must be >= 1, or 0 for auto")
    if args.max_prefill_tokens < 0:
        raise ValueError("--max-prefill-tokens must be >= 0")
    if args.speculative_ngram < 0:
        raise ValueError("--speculative-ngram must be >= 0")
    if getattr(args, "token_budget", 0) < 0:
        raise ValueError("--token-budget must be >= 0, or 0 for auto")
    if getattr(args, "packed_serving", "off") == "on":
        if getattr(args, "pipeline_decode", "off") == "on":
            raise ValueError(
                "--packed-serving is incompatible with --pipeline-decode "
                "(a packed step would race the in-flight chunk)"
            )
        gang = getattr(args, "num_processes", 0) or int(
            os.environ.get("FMA_NUM_PROCESSES", "0") or 0
        )
        if gang > 1:
            raise ValueError(
                "--packed-serving is incompatible with multi-host gangs "
                "(the per-step packing layout is too large for the "
                "lockstep control frame); sharded single-process meshes "
                "via --tensor-parallel-size compose fine"
            )
    if getattr(args, "speculative_mtp", 0):
        if getattr(args, "packed_serving", "off") == "on":
            raise ValueError(
                "--speculative-mtp is incompatible with --packed-serving "
                "(the mixed program emits one token a slot a step)"
            )
        gang = getattr(args, "num_processes", 0) or int(
            os.environ.get("FMA_NUM_PROCESSES", "0") or 0
        )
        if gang > 1:
            raise ValueError(
                "--speculative-mtp is incompatible with multi-host gangs "
                "(the lockstep frame carries no drafts)"
            )
    if getattr(args, "slo_ttft_ms", 0.0) < 0:
        raise ValueError("--slo-ttft-ms must be >= 0 (0 = off)")
    if getattr(args, "slo_tpot_ms", 0.0) < 0:
        raise ValueError("--slo-tpot-ms must be >= 0 (0 = off)")
    if getattr(args, "arrival_ewma_tau_s", 30.0) <= 0:
        raise ValueError("--arrival-ewma-tau-s must be > 0")
    if not 0.0 <= getattr(args, "trace_requests", 0.0) <= 1.0:
        raise ValueError("--trace-requests must be in [0, 1]")
    if getattr(args, "model_pool_mib", 0) < 0:
        raise ValueError("--model-pool-mib must be >= 0")
    if getattr(args, "swap_bucket_mib", 1) < 1:
        raise ValueError("--swap-bucket-mib must be >= 1")
    sq = getattr(args, "sleep_quant", "off") or "off"
    if sq != "off":
        from ..models import quant as transfer_quant

        reason = transfer_quant.transfer_quant_supported(sq)
        if reason:
            raise ValueError(f"--sleep-quant {sq}: {reason}")
        if getattr(args, "quantization", ""):
            raise ValueError(
                "--sleep-quant composes with full-precision serving only: "
                "a --quantization int8 engine already holds (and moves) "
                "int8 weights"
            )
        if (
            getattr(args, "num_processes", 0)
            or int(os.environ.get("FMA_NUM_PROCESSES", "0") or 0)
        ) > 1:
            raise ValueError(
                "--sleep-quant is not supported for multi-host gangs "
                "(gang offloads stage per-shard and reassemble "
                "bit-for-bit); single-process --tensor-parallel-size "
                "meshes compose fine"
            )
    if getattr(args, "zero_drain", "off") == "on":
        gang = getattr(args, "num_processes", 0) or int(
            os.environ.get("FMA_NUM_PROCESSES", "0") or 0
        )
        if gang > 1:
            raise ValueError(
                "--zero-drain is not supported for multi-host gangs "
                "(parked request bundles are process-local; gang "
                "actuation keeps today's abort semantics)"
            )
    if getattr(args, "pool_disk_mib", 0) < 0:
        raise ValueError("--pool-disk-mib must be >= 0")
    if getattr(args, "exec_pool_mib", 0) < 0:
        raise ValueError("--exec-pool-mib must be >= 0")
    from .exec_pool import parse_warmup_buckets

    parse_warmup_buckets(getattr(args, "warmup_buckets", ""))
    if getattr(args, "load_workers", 0) < 0:
        raise ValueError("--load-workers must be >= 0 (0 = auto)")
    if getattr(args, "load_inflight_mib", 1) < 1:
        raise ValueError("--load-inflight-mib must be >= 1")
    if getattr(args, "prefetch_mib_s", 0) < 0:
        raise ValueError("--prefetch-mib-s must be >= 0 (0 = unthrottled)")
    if getattr(args, "faults", ""):
        try:
            faults.parse_spec(args.faults)
        except ValueError as e:
            raise ValueError(f"--faults: {e}")
    if args.port <= 0 or args.port > 65535:
        raise ValueError(f"invalid port {args.port}")


def parse_engine_options(options: str) -> argparse.Namespace:
    args, unknown = make_arg_parser().parse_known_args(shlex.split(options or ""))
    if unknown:
        raise ValueError(f"unknown engine options: {unknown}")
    validate_parsed_args(args)
    return args


class ProfileConflict(Exception):
    """POST /v1/profile while a capture is running (jax.profiler is
    process-global: exactly one concurrent capture), or DELETE with none."""


class MigrationRejected(Exception):
    """A migration verb's precondition failed with nothing displaced —
    identity mismatch, no capacity, spent fence token (the double-resume
    refusal) — surfaced as 409:
    the orchestrator picks another destination or leaves the streams
    where they are."""


class MigrationFailed(Exception):
    """A migration step failed AFTER recovery ran: export failure with
    the streams resumed locally, import failure with the destination
    rolled back clean, or an injected lost ack. Surfaced as 500; the
    fence makes the orchestrator's retry safe."""


class _RateEWMA:
    """Exponentially-decayed event rate (events/second).

    Each arrival adds ``1/tau`` and the estimate decays by
    ``exp(-dt/tau)`` between observations, so a Poisson stream of rate
    lambda converges to lambda regardless of scrape cadence — and the
    estimate keeps decaying toward zero after traffic stops (reading is
    side-effect free on the event count). Not thread-safe; callers hold
    the service's SLO lock."""

    def __init__(self, tau_s: float = 30.0) -> None:
        self.tau_s = max(1e-6, float(tau_s))
        self._rate = 0.0
        self._t: Optional[float] = None

    def _decay(self, now: float) -> None:
        if self._t is None:
            self._t = now
            return
        dt = now - self._t
        if dt > 0:
            import math

            self._rate *= math.exp(-dt / self.tau_s)
            self._t = now

    def observe(self, now: float) -> None:
        self._decay(now)
        self._rate += 1.0 / self.tau_s

    def rate(self, now: float) -> float:
        self._decay(now)
        return self._rate


def _pool_key(model: str, checkpoint_dir: str) -> str:
    """Identity of a pooled model: the same model name restored from a
    different checkpoint is a different set of weights."""
    return f"{model}@{checkpoint_dir}" if checkpoint_dir else model


@dataclass
class _PrefetchedWeights:
    """A pool entry staged by background prefetch (POST /v1/prefetch):
    host-resident plain numpy weights in cfg.dtype — no engine, no device
    state, no compiled programs. A swap to it skips the checkpoint read
    (source="pool") and only pays compile + the H2D stream; eviction is
    just dropping the reference."""

    model_id: str
    checkpoint_dir: str
    params_host: Optional[Dict[str, Any]]
    nbytes: int
    #: flat weight key -> content digest (engine/chunk_store.py): what the
    #: tiered pool dedupes on; carried into the runtime a swap builds
    digests: Optional[Dict[str, str]] = None
    #: --sleep-quant staging: params_host leaves are int8/fp8 payloads and
    #: this is the aligned TransferQuant-or-None list (models/quant.py) —
    #: the consuming swap streams payloads and dequantizes on device
    quant_metas: Optional[list] = None
    quant_mode: str = "off"


@dataclass
class _ModelRuntime:
    """Everything model-specific the service owns: swapping models means
    swapping this bundle. A pooled (slept) runtime keeps its engine object
    — and with it the compiled programs, which are host-resident — so a
    swap-back recompiles nothing and re-reads no checkpoint."""

    model_id: str
    engine: InferenceEngine
    sleeper: Any
    tokenizer: Any
    hf_dir: str
    checkpoint_dir: str
    #: flat weight key -> content digest, computed once at load (None for
    #: random-init/sharded/quantized builds): drives the delta-swap's
    #: device-array reuse and the pool's cross-variant dedup
    digests: Optional[Dict[str, str]] = None
    #: zero-drain actuation (engine/parked.py): the ParkedRequests bundle
    #: this runtime's preempted live work was paged into — stored with
    #: the slept weights, byte-counted against the pool budget, resumed
    #: on wake/swap-back (None = nothing parked)
    parked: Optional[Any] = None


class EngineService:
    """Thread-hosted engine with an async-facing submit/sleep/swap API."""

    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self._lock = threading.Lock()  # serializes device work vs sleep edges
        #: admin calls (sleep/wake/swap) waiting on the step lock: the
        #: engine loop re-acquires it hot (back-to-back steps), which can
        #: starve a parked waiter for a whole generation — the loop yields
        #: briefly when this is non-zero so the admin op lands promptly.
        #: Counter updates are guarded: a lost update from two racing
        #: admin calls would leave it non-zero (or negative) forever.
        self._admin_waiting = 0
        self._admin_count_lock = threading.Lock()
        self._new_work = threading.Event()
        self._stop = False
        self._futures: Dict[int, concurrent.futures.Future] = {}
        self._fut_seq: Dict[int, int] = {}  # id(future) -> seq_id
        self._pending: List[Any] = []
        self._abort_q: List[Any] = []  # futures whose client went away
        self.failure: Optional[str] = None
        #: a recoverable failure happened and was healed in-process (e.g.
        #: a rolled-back swap): /health stays 200 but reports DEGRADED
        #: with this reason until the next successful admin edge clears it
        self.degraded: Optional[str] = None
        #: last-mirrored engine pad-waste byte totals per dispatch path —
        #: the engine keeps cumulative ints, Prometheus wants increments
        self._pad_waste_seen: Dict[str, int] = {}
        self._step_h2d_seen: Dict[str, int] = {}
        self.started_at = time.monotonic()
        # Request-lifecycle SLO/goodput accounting (docs/perf.md "Fleet
        # benchmarking and goodput"): targets in seconds (0 = off), plain
        # counters mirrored into Prometheus and served whole by GET
        # /v1/stats — the one-call instance row the launcher's fleet
        # rollup aggregates. Guarded by _slo_mu: submit() runs on the
        # event loop, _observe_finished on the engine thread, stats() on
        # executor threads.
        self._slo_ttft_s = max(0.0, getattr(args, "slo_ttft_ms", 0.0)) / 1e3
        self._slo_tpot_s = max(0.0, getattr(args, "slo_tpot_ms", 0.0)) / 1e3
        self._slo_mu = threading.Lock()
        self._slo_met = 0
        self._slo_violated = 0
        self._goodput_tokens = 0
        self._generated_tokens = 0
        self._finished_requests = 0
        #: per-cause abort counts (client | swap | state_loss), the
        #: /v1/stats mirror of fma_engine_aborted_requests_total
        self._aborted: Dict[str, int] = {}
        #: actuation edges this process performed (swap | sleep | wake):
        #: with uptime, the fleet rollup's actuations/hour
        self._actuations: Dict[str, int] = {}
        # Zero-drain actuation (docs/perf.md "Zero-drain actuation"):
        # preempt/park/resume counters mirrored into /v1/stats. Guarded
        # by _slo_mu like the rest of the lifecycle accounting.
        self._zero_drain = getattr(args, "zero_drain", "off") == "on"
        self._zd_preempted = 0
        self._zd_resumed = 0
        self._zd_aborted = 0
        self._zd_parked_bytes = 0
        self._zd_migrated = 0
        # Live request migration (ROADMAP item 3a; docs/operations.md
        # "Draining a node without dropping streams"). Source side: at
        # most ONE in-flight export — the fenced bundle awaiting the
        # import ack — plus the set of spent fence tokens (single-use:
        # a spent token can neither release nor locally resume again,
        # which is what makes double-resume a 409, never a duplicate
        # stream). Destination side: stored import acks keyed by fence
        # token (a lost-ack retry replays the stored response instead
        # of seating twice) and the claim table the source's result
        # watchers poll. Counters are _slo_mu-guarded like the rest.
        self._migration: Optional[Dict[str, Any]] = None
        self._migration_gen = 0
        self._spent_fences: set = set()
        self._import_acks: Dict[str, Dict[str, Any]] = {}
        self._imported_claims: Dict[str, Dict[str, Any]] = {}
        self._mig = {
            "exported": 0, "imported": 0, "committed": 0,
            "resumed_local": 0, "rolled_back": 0, "state_loss": 0,
            "requests_out": 0, "requests_in": 0,
            "bytes_out": 0, "bytes_in": 0,
        }
        # Request-lifecycle tracing (docs/tracing.md "request.* spans"):
        # head-sampling fraction applied at submit; tail-keep (violated /
        # aborted / migrated) decided at completion. The exemplar deque
        # pairs each retained violation with its leg breakdown so
        # /v1/stats can answer "which leg" without a trace fetch.
        self._trace_frac = max(
            0.0, min(1.0, getattr(args, "trace_requests", 0.0) or 0.0)
        )
        tracing.configure_request_sampling(self._trace_frac)
        self._slo_exemplars: deque = deque(
            maxlen=int(os.environ.get("FMA_SLO_EXEMPLARS", "16") or 16)
        )
        # Migrated-away streams whose client is still attached: id(fut)
        # -> {"dest", "claim"}, registered when the claim watcher starts
        # and popped (idempotently) on every watcher exit path. This is
        # what lets a client disconnect AFTER migration resolve to
        # exactly one abort on each instance (the source counts
        # reason="client" here; the destination counts its own when the
        # claim-abort notification lands).
        self._proxied: Dict[int, Dict[str, Any]] = {}
        self._arrival = _RateEWMA(
            getattr(args, "arrival_ewma_tau_s", 30.0) or 30.0
        )
        # Actuation cost oracle + decision flight recorder
        # (utils/costs.py; docs/operations.md "Pricing an actuation"):
        # per-kind bandwidth EWMAs fed by every transfer path
        # (sleep/wake/swap windows via the SleepManager's on_transfer
        # hook, cold loads via LoadStats.transfer_figures) — surviving
        # across actuations here — plus the bounded ring of
        # predicted-vs-actual records GET /v1/actuations serves.
        from ..utils.costs import CostBook

        self.costs = CostBook(
            capacity=int(
                os.environ.get("FMA_FLIGHT_RECORDER_CAP", "512") or 512
            )
        )
        # Fault-injection arming (utils/faults.py): env first, then the
        # flag — both before the first build so coldload points can fire
        # on the initial model too.
        faults.load_env()
        if getattr(args, "faults", ""):
            faults.arm_spec(args.faults)

        dist = resolve_distributed(args)
        if dist is not None and args.tensor_parallel_size <= 1:
            # an unsharded multi-process engine would device_put onto
            # non-addressable global devices; the gang contract is SPMD
            # over the whole slice
            raise ValueError(
                "multi-host engine requires --tensor-parallel-size equal "
                "to the global chip count (got "
                f"{args.tensor_parallel_size})"
            )
        if dist is not None:
            # Must run before any device/backend touch: every process of the
            # gang joins the coordination service, and jax.devices() becomes
            # the GLOBAL device set. initialize() blocks until all
            # num_processes join — so this engine reporting healthy implies
            # the whole multi-host gang formed.
            import jax

            if "cpu" in (os.environ.get("JAX_PLATFORMS") or "").lower():
                # The XLA CPU client ships WITHOUT cross-process
                # collectives by default: a CPU gang forms, then the first
                # sharded device_put dies with "Multiprocess computations
                # aren't implemented on the CPU backend" (the leader exits
                # 1, the follower aborts on the lost coordinator). The
                # gloo backend jaxlib bundles makes CPU gangs real — the
                # e2e multihost tests and any CPU rehearsal of a TPU
                # topology depend on it. TPU runs never enter here.
                try:
                    jax.config.update(
                        "jax_cpu_collectives_implementation", "gloo"
                    )
                except Exception:  # noqa: BLE001 — gloo-less jaxlib
                    logger.warning(
                        "this jaxlib has no CPU collectives backend; "
                        "a multi-process CPU gang will fail at the first "
                        "sharded computation"
                    )
            jax.distributed.initialize(**dist)
        # Multi-host lockstep roles (engine/multihost.py): process 0 leads
        # (serves + broadcasts control frames); others follow (replay).
        self.process_id = dist["process_id"] if dist else 0
        self.is_follower = dist is not None and self.process_id > 0
        #: any member of a multi-host gang (leader included): gangs never
        #: carry AOT executables — their scheduler arrays keep the legacy
        #: uncommitted placement (engine._sched_sharding), so a warmed
        #: executable's replicated-NamedSharding avals could never match,
        #: and a leader-AOT/follower-jit split would desync the lockstep
        self.is_gang = dist is not None
        self.watchdog = None
        hb_timeout = float(
            os.environ.get("FMA_GANG_HEARTBEAT_TIMEOUT", "20") or 0
        )
        if dist is not None and hb_timeout > 0:
            # Data-plane failure detection (engine/multihost.py): a dead
            # gang member must become a non-zero exit on every other
            # member within the timeout — collectives can't unwind a
            # wedged lockstep in-process. FMA_GANG_HEARTBEAT_TIMEOUT=0
            # disables (tests that kill members deliberately).
            # Started HERE — right after jax.distributed.initialize,
            # before any checkpoint load — so members heartbeat (and
            # answer probes) through the whole engine init: cross-host
            # init skew from one host cold-loading a multi-GB checkpoint
            # no longer burns FMA_GANG_JOIN_GRACE and tears down a
            # healthy forming gang. The grace now only has to cover the
            # distributed client forming itself.
            from .multihost import GangWatchdog

            self.watchdog = GangWatchdog(
                process_id=self.process_id,
                num_processes=dist["num_processes"],
                coordinator_address=dist["coordinator_address"],
                timeout=hb_timeout,
                join_grace=float(
                    os.environ.get("FMA_GANG_JOIN_GRACE", "60") or 60
                ),
            )
            self.watchdog.start()
        # Tiered host model pool + chunked-transfer sizing (docs/engine.md
        # "Model hot-swap", docs/perf.md "Tiered weight cache and delta
        # swap"): models swapped out stay host-resident up to the budget —
        # content-addressed so sibling fine-tunes dedupe their shared
        # tensors and swaps between them move only the delta — with a
        # local-disk spill tier below for evicted models' chunks.
        # Content hashing covers single-device AND single-process tp
        # meshes: sharded entries carry mesh-qualified digests (the
        # content hash shard-qualified with mesh shape + per-leaf
        # sharding spec — chunk_store.qualify_digest), computed from the
        # same per-process host views the sleeper stages, so sibling
        # variants on one mesh dedupe and delta-swap exactly like
        # single-device ones. Off for multi-host gangs (no host-resident
        # global trees to hash) and for --quantization engines (the
        # serving tree's {"q","s"} leaves have no stable identity).
        self._content_hash = (
            getattr(args, "content_hash", "on") == "on"
            and dist is None
            and not getattr(args, "quantization", "")
        )
        # Compressed actuation transfers (docs/perf.md "Compressed
        # actuation"): opt-in int8/fp8 sleep/wake/swap via models/quant.py.
        # Single-process only — gang offloads stage per-shard bit-for-bit.
        self._sleep_quant = getattr(args, "sleep_quant", "off") or "off"
        self._sleep_quant_hot_head = (
            getattr(args, "sleep_quant_hot_head", "on") != "off"
        )
        if self._sleep_quant != "off" and dist is not None:
            raise ValueError(
                "--sleep-quant is not supported for multi-host gangs"
            )
        from .chunk_store import ChunkStore, default_disk_dir

        chunks = None
        if self._content_hash:
            chunks = ChunkStore(
                disk_dir=getattr(args, "pool_disk_dir", "")
                or default_disk_dir(),
                disk_budget_bytes=max(0, getattr(args, "pool_disk_mib", 4096))
                << 20,
                on_event=self._pool_tier_event,
            )
        self.model_pool = HostModelPool(
            budget_bytes=max(0, getattr(args, "model_pool_mib", 4096)) << 20,
            chunks=chunks,
        )
        self._swap_bucket_bytes = (
            max(1, getattr(args, "swap_bucket_mib", 256)) << 20
        )
        # AOT executable pool + warmup plan (engine/exec_pool.py): compiled
        # programs pooled beside the host model pool, with spill into the
        # launcher's persistent compile-cache dir so entries survive
        # instance restarts (docs/perf.md "Warmup and the executable pool").
        from .exec_pool import (
            ExecutablePool,
            default_spill_dir,
            parse_warmup_buckets,
        )

        self._warmup_buckets = parse_warmup_buckets(
            getattr(args, "warmup_buckets", "")
        )
        self.exec_pool = ExecutablePool(
            budget_bytes=max(0, getattr(args, "exec_pool_mib", 256)) << 20,
            spill_dir=default_spill_dir(),
            on_event=self._exec_pool_event,
        )
        #: the most recent WarmupTask (observability + tests: abort-on-
        #: cancellation and hidden-compile accounting are asserted on it)
        self._last_warmup: Optional[Any] = None
        #: cold runtime builds (checkpoint / HF read or random init); a
        #: pool hit on swap does NOT increment it — the zero-re-read
        #: contract the swap e2e test pins
        self.builds_total = 0
        self.last_swap: Dict[str, Any] = {}
        #: filled by every _build_runtime (h2d_s / bytes_in / buckets_in /
        #: overlap): what a pool-miss swap reports instead of zeros
        self._last_build_stats: Dict[str, Any] = {}
        # Background checkpoint prefetch (POST /v1/prefetch): one staging
        # thread at a time, host-only, abortable.
        self._prefetch_mu = threading.Lock()
        self._prefetch_thread: Optional[threading.Thread] = None
        self._prefetch_abort = threading.Event()
        self.last_prefetch: Dict[str, Any] = {"state": "idle"}
        # On-demand deep profiling (POST/DELETE /v1/profile): one
        # concurrent jax.profiler capture per process.
        self._profile_mu = threading.Lock()
        self._profile_dir: Optional[str] = None
        self._profile_span: Any = tracing.NOOP_SPAN
        # Release-on-sleep is resolved BEFORE the first build: zero-drain
        # parking is off for device-releasing sleeps (the park's host
        # bundle survives, but the restore contract is the full-state
        # numpy staging path), and the built engine's zero_drain_park
        # flag — which pricing peeks read — depends on this answer.
        import jax  # deliberately not module-level: parse-time must not touch a backend

        # the first touch of the backend: PJRT client, libtpu (near 0 where
        # the entry point already made the client)
        with tracing.stage("start.backend"):
            backend = jax.default_backend()
        mode = getattr(args, "sleep_release_devices", "auto")
        self.release_on_sleep = (
            mode == "always" or (mode == "auto" and backend == "tpu")
        )
        if dist is not None:
            # gang sleep is offload-only: device release would require
            # every process to drop and re-join the distributed client in
            # lockstep (engine/sleep.py raises on it)
            self.release_on_sleep = False
        if self._zero_drain and dist is not None:
            raise ValueError(
                "--zero-drain is not supported for multi-host gangs"
            )
        # Under run_server's engine.start span, which parents on
        # FMA_TRACEPARENT when the spawning launcher stamped one
        # (utils/tracing.py:startup_begin): the child's initial build joins
        # the create-instance trace across the fork.
        self._install_runtime(
            self._build_runtime(
                args.model, getattr(args, "checkpoint_dir", "") or ""
            )
        )
        # first flight-recorder row: the initial cold build — trigger
        # "restart" when a supervising launcher re-spawned this child
        # (launcher/instance.py stamps FMA_RESTARTED around the fork), so
        # the recorder distinguishes crash-loop churn from client-driven
        # actuation
        self._record_actuation(
            "coldload",
            args.model,
            trigger=(
                "restart" if os.environ.get("FMA_RESTARTED") else "startup"
            ),
            tier="cold",
            pred=None,
            actual_bytes=self._last_build_stats.get("bytes_in", 0),
            actual_s=self._last_build_stats.get("h2d_s", 0.0),
        )
        # what /v1/stats reports as the device: read once, while the client
        # is certainly up (stats must answer while the devices are released)
        dev = jax.devices()[0]
        self._device_info = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": jax.device_count(),
        }
        logger.info(
            "serving %s on %s; attention impl %s",
            args.model, self._device_info,
            self.engine.attention_impl,
        )
        if dist is not None and not self.is_follower:
            from .multihost import LockstepLeader

            self.engine.lockstep = LockstepLeader(self.engine)
        self._publisher = self._make_publisher()
        self._publish_usage()
        tracing.describe_slow_entries_with(self._describe_slow_entry)
        self._thread = threading.Thread(
            target=self._run_follower if self.is_follower else self._run,
            daemon=True,
            name="engine-loop",
        )
        self._thread.start()

    def _describe_slow_entry(self, overlapped: bool) -> str:
        """What a slow scheduler entry's log line says of the engine
        (utils/tracing.py:_count_slow; scheduler thread only)."""
        eng = self.engine
        return "live sequences %d, waiting %d, in flight: %s" % (
            sum(1 for s in eng._slots if s is not None),
            len(eng._waiting),
            "chunk" if eng.chunk_in_flight
            else "prefill" if overlapped else "nothing",
        )

    def _count_abort(self, cause: str, n: int = 1) -> None:
        """One abort-accounting choke point: the Prometheus counter's
        ``reason`` label and the /v1/stats mirror move together, so the
        fleet harness can attribute SLO violations to actuation
        preemption (swap/state_loss) vs client behavior."""
        if n <= 0:
            return
        ENGINE_ABORTS.labels(model=self.args.model, reason=cause).inc(n)
        with self._slo_mu:
            self._aborted[cause] = self._aborted.get(cause, 0) + n

    def _bump_actuation(self, kind: str) -> None:
        with self._slo_mu:
            self._actuations[kind] = self._actuations.get(kind, 0) + 1

    def _abort_engine_work(
        self, reason: str, exc: Exception, cause: str = "state_loss"
    ) -> int:
        """Abort everything waiting or in flight in the engine and fail the
        matching futures (state-loss edges: level-2 wake, model swap).
        Caller holds the step lock."""
        aborted = self.engine.abort_all(reason)
        self._count_abort(cause, len(aborted))
        now = time.monotonic()
        for req in aborted:
            self._finish_request_trace(
                req, now, aborted=True, outcome=cause
            )
            fut = self._futures.pop(req.seq_id, None)
            if fut is not None:
                self._fut_seq.pop(id(fut), None)
                if not fut.done():
                    fut.set_exception(exc)
        return len(aborted)

    def _free_pooled(self, victims, why: str) -> None:
        """Release evicted pool entries' pinned-host bytes: escalating the
        slept runtime to level 2 is exactly 'drop the host copy'."""
        ENGINE_POOL_EVICTIONS.inc(len(victims))
        for victim in victims:
            rt = victim.runtime
            bundle = getattr(rt, "parked", None)
            if bundle is not None:
                # the parked requests' KV dies with the evicted entry:
                # resolve them to a clean state_loss abort, never a
                # future that hangs forever
                rt.parked = None
                self._abort_parked_bundle(
                    bundle,
                    getattr(rt, "model_id", self.args.model),
                    f"preempted requests lost: parked model evicted "
                    f"({why})",
                )
            if isinstance(rt, _PrefetchedWeights):
                # staged host numpy: dropping the reference IS the free
                rt.params_host = None
                continue
            try:
                rt.sleeper.sleep(2)
            except Exception:
                logger.warning(
                    "failed to free pooled model %s (%s)",
                    victim.model_id, why, exc_info=True,
                )

    def _pool_tier_event(self, kind: str) -> None:
        """Mirror chunk-store tier traffic into Prometheus (the store
        never imports prometheus)."""
        ENGINE_POOL_TIER_EVENTS.labels(event=kind).inc()

    def _pool_park(
        self, key: str, runtime: Any, nbytes: int
    ) -> List[Any]:
        """Pool a runtime (or staged-weights bundle) under `key`,
        interning its digested weight leaves into the content-addressed
        chunk store first — so a sibling variant already pooled shares its
        common tensors instead of duplicating them, and an eviction later
        leaves a manifest the disk tier can serve. Returns the evicted
        entries (the caller frees them via _free_pooled)."""
        chunk_digests: List[str] = []
        interned = 0
        weight_digests = None
        if self._content_hash and self.model_pool.budget_bytes > 0:
            from ..models import quant as transfer_quant

            if isinstance(runtime, _PrefetchedWeights):
                if runtime.quant_metas is not None:
                    # quantized staging: payloads intern under TRANSFER
                    # digests (disjoint space — a payload must never be
                    # handed out as the fp tensor it approximates), with
                    # no eviction manifest; "q:" digests spill to disk
                    # like fp chunks — the spill header's content hash
                    # makes the reload verifiable
                    if runtime.params_host is not None:
                        qmap = transfer_quant.transfer_digest_map(
                            runtime.params_host,
                            runtime.quant_metas,
                            prefix="",
                        )
                        (
                            runtime.params_host,
                            chunk_digests,
                            interned,
                        ) = self.model_pool.intern_tree(
                            runtime.params_host, qmap, prefix=""
                        )
                elif runtime.digests and runtime.params_host is not None:
                    (
                        runtime.params_host,
                        chunk_digests,
                        interned,
                    ) = self.model_pool.intern_tree(
                        runtime.params_host, runtime.digests, prefix=""
                    )
                    weight_digests = runtime.digests
            else:
                digests = getattr(runtime, "digests", None)
                host_state = getattr(runtime.sleeper, "_host_state", None)
                quant_metas = getattr(runtime.sleeper, "_quant_meta", None)
                if quant_metas is not None and host_state is not None:
                    # quantized slept runtime: quantized leaves under
                    # disk-spillable "q:" transfer digests, untouched
                    # hot-head leaves under their fp digests (correct
                    # content — they dedupe AND spill with fp siblings);
                    # no eviction manifest
                    qmap = transfer_quant.transfer_digest_map(
                        host_state, quant_metas, prefix="params"
                    )
                    merged = dict(qmap)
                    for k, d in (digests or {}).items():
                        if k not in merged:
                            merged[k] = d
                    (
                        new_tree,
                        chunk_digests,
                        interned,
                    ) = self.model_pool.intern_tree(
                        host_state, merged, prefix="params"
                    )
                    runtime.sleeper._host_state = new_tree
                elif digests and host_state is not None:
                    (
                        new_tree,
                        chunk_digests,
                        interned,
                    ) = self.model_pool.intern_tree(
                        host_state, digests, prefix="params"
                    )
                    runtime.sleeper._host_state = new_tree
                    weight_digests = digests
        if not chunk_digests:
            # nothing interned (e.g. TPU pinned-host staging, whose jax
            # arrays are client-owned): an eviction manifest would be
            # guaranteed-dead — every chunk a miss — and would only crowd
            # resolvable manifests out of the bounded registry
            weight_digests = None
        return self.model_pool.put(
            key,
            runtime,
            nbytes=nbytes,
            chunk_digests=chunk_digests,
            weight_digests=weight_digests,
            interned_bytes=interned,
        )

    def _exec_pool_event(self, kind: str) -> None:
        """Mirror executable-pool traffic into Prometheus (the pool itself
        never imports prometheus)."""
        if kind == "hit":
            ENGINE_EXEC_POOL_HITS.inc()
        elif kind == "miss":
            ENGINE_EXEC_POOL_MISSES.inc()
        elif kind == "eviction":
            ENGINE_EXEC_POOL_EVICTIONS.inc()

    def _start_warmup(
        self, model_id: str, resolved: Optional[tuple] = None
    ) -> Optional[Any]:
        """Kick the AOT warmup task for an incoming `model_id` (None =
        warmup disabled or unsupported): resolves the incoming config
        exactly like the build will and starts compiling on a background
        thread (engine/exec_pool.py). Callers that already ran
        ``_resolve_model`` pass its tuple as ``resolved`` — the resolve
        loads the tokenizer from disk, which must not run twice on the
        swap critical path. Never raises — warmup must never fail a swap;
        worst case the build falls back to first-touch jit."""
        if not self._warmup_buckets:
            return None
        if self.is_gang:
            # no gang member carries AOT entries — followers replay the
            # leader's dispatches through jit, and a leader running AOT
            # programs against follower jit recompiles could desync the
            # lockstep (see is_gang)
            return None
        try:
            if resolved is None:
                resolved = self._resolve_model(model_id)
            model_cfg, eos, extra_eos = resolved[0], resolved[1], resolved[2]
            cfg = self._engine_cfg_for(model_cfg, eos, extra_eos)
            from .exec_pool import WarmupTask

            mesh = None
            if self.args.tensor_parallel_size > 1:
                # the same mesh the build will construct (Mesh equality
                # is by devices + axis names, so the warmed executables'
                # NamedSharding avals match the built engine's arrays)
                from ..parallel.mesh import serving_mesh

                mesh = serving_mesh(self.args.tensor_parallel_size)
            task = WarmupTask(
                cfg,
                self._warmup_buckets,
                pool=self.exec_pool,
                mesh=mesh,
                trace_parent=tracing.current_context(),
                on_program=lambda program, secs: ENGINE_WARMUP_SECONDS.labels(
                    program=program
                ).set(secs),
            )
            self._last_warmup = task
            return task
        except Exception:  # noqa: BLE001 — warmup is strictly best-effort
            logger.warning(
                "AOT warmup start failed for %s", model_id, exc_info=True
            )
            return None

    def _reinstall_executables(self) -> int:
        """Wake re-validates the executable pool instead of recompiling:
        pool entries for the engine's config (including spill reloads,
        where reload is trusted) are reinstalled into the engine's AOT
        table; anything missing jit-compiles on first touch through the
        persistent cache — the pre-existing wake behavior."""
        if not self._warmup_buckets or self.is_gang:
            return 0
        from .exec_pool import exec_key, exec_signature, mesh_shape, warmup_plan

        eng = self.engine
        try:
            sig = exec_signature(eng.cfg, mesh_shape(eng.mesh))
        except Exception:  # noqa: BLE001 — revalidation is best-effort
            return 0
        n = 0
        for program, bucket in warmup_plan(eng.cfg, self._warmup_buckets):
            if (program, bucket) in eng._aot:
                continue
            compiled = self.exec_pool.get(exec_key(sig, program, bucket))
            if compiled is not None:
                eng.install_executable(program, bucket, compiled)
                n += 1
        return n

    @contextlib.contextmanager
    def _admin_lock(self):
        """The step lock, for admin edges (sleep/wake/swap): registers as a
        waiter so the engine loop hands the lock over between steps instead
        of re-acquiring it hot (an unfair lock can otherwise starve the
        admin call until the whole running generation finishes)."""
        with self._admin_count_lock:
            self._admin_waiting += 1
        try:
            with self._lock:
                yield
        finally:
            with self._admin_count_lock:
                self._admin_waiting -= 1

    # -- model runtimes (build / install / hot-swap) -------------------------

    def _resolve_model(self, model_id: str):
        """Config + tokenizer + eos identity for `model_id` — shared by
        the cold build AND the AOT warmup driver, which must derive the
        SAME program shapes (the decode-chunk program embeds the eos id,
        so a divergent resolution would compile the wrong program).
        Returns (model_cfg, eos_token_id, extra_eos, hf_dir, tokenizer)."""
        args = self.args
        hf_dir = ""
        eos_token_id = args.eos_token_id
        extra_eos: tuple = ()
        if model_id.startswith("hf:"):
            from ..models import hf as hf_models

            hf_dir = model_id[3:]
            model_cfg = hf_models.config_from_hf(
                hf_dir, quantization=args.quantization or ""
            )
            if eos_token_id < 0:
                all_eos = hf_models.eos_token_ids_from_hf(hf_dir)
                if all_eos:
                    # Llama-3-Instruct style multi-eos: chat turns end
                    # with <|eot_id|>, not the primary eos
                    eos_token_id = all_eos[0]
                    extra_eos = tuple(all_eos[1:])
        else:
            model_cfg = MODEL_CONFIGS[model_id]()
            if args.quantization and model_cfg.quantization != args.quantization:
                import dataclasses

                model_cfg = dataclasses.replace(
                    model_cfg, quantization=args.quantization
                )
        from . import tokenizer as tokenizer_mod

        tok_path = getattr(args, "tokenizer", "") or ""
        if (
            not tok_path
            and hf_dir
            and tokenizer_mod.has_tokenizer_files(hf_dir)
        ):
            tok_path = hf_dir
        tokenizer = tokenizer_mod.load_tokenizer(tok_path)
        if eos_token_id < 0 and hf_dir:
            # last resort: the tokenizer knows its eos even when neither
            # config.json nor generation_config.json declares one
            eos_token_id = (
                tokenizer.eos_token_id
                if tokenizer.eos_token_id is not None
                else -1
            )
        return model_cfg, eos_token_id, extra_eos, hf_dir, tokenizer

    def _engine_cfg_for(
        self, model_cfg, eos_token_id: int, extra_eos: tuple
    ) -> EngineConfig:
        """The EngineConfig a runtime for `model_cfg` gets — one
        definition, so the warmup driver's AOT compiles and the engine's
        lazy jit always describe the same programs."""
        args = self.args
        import jax  # deliberately not module-level: parse-time must not touch a backend

        from .engine import refuse_slot_state, slot_state_kinds

        if self._zero_drain:
            refuse_slot_state(model_cfg, "a zero-drain park (--zero-drain on)")
        prefix_caching = args.prefix_caching == "on" or (
            args.prefix_caching == "auto" and not slot_state_kinds(model_cfg)
        )
        return EngineConfig(
            model=model_cfg,
            max_batch=args.max_batch,
            page_size=args.page_size,
            num_pages=args.num_pages,
            max_seq_len=args.max_model_len or 0,
            eos_token_id=eos_token_id,
            extra_eos_ids=extra_eos,
            attention_impl=resolve_attention_impl(
                args.attention_impl, model_cfg, args.tensor_parallel_size
            ),
            decode_chunk=args.decode_chunk
            or (32 if jax.default_backend() == "tpu" else 8),
            pipeline_decode=(
                getattr(args, "pipeline_decode", "off") == "on"
            ),
            drain_tail=getattr(args, "drain_tail", "auto"),
            prefix_caching=prefix_caching,
            max_prefill_tokens=args.max_prefill_tokens,
            speculative_ngram=args.speculative_ngram,
            speculative_mtp=getattr(args, "speculative_mtp", 0),
            logprobs_topk=max(0, getattr(args, "logprobs_topk", 5)),
            packed_serving=(
                getattr(args, "packed_serving", "off") == "on"
            ),
            token_budget=getattr(args, "token_budget", 0),
        )

    def _qualify_digests(
        self, digests: Optional[Dict[str, str]], model_cfg
    ) -> Optional[Dict[str, str]]:
        """Shard-qualify a flat content-digest map for this engine's mesh
        placement (no-op single-device): each digest becomes
        ``m:<hash(tp|spec)>:<content>`` (chunk_store.qualify_digest) with
        the per-leaf sharding spec derived from the MODEL CONFIG's
        logical axes — the same rule table shard_pytree places with — so
        the host-only prefetch staging path and the placed build qualify
        identically, and a digest can only ever match content under the
        same mesh shape AND the same per-leaf spec. Idempotent on
        already-qualified maps (tier manifests carried through
        take_staged)."""
        tp = self.args.tensor_parallel_size
        if not digests or tp <= 1:
            return digests
        from ..models.registry import logical_axes_for
        from ..parallel.mesh import flat_spec_strs
        from .chunk_store import qualify_digest

        specs = flat_spec_strs(logical_axes_for(model_cfg))
        missing = [k for k in digests if k not in specs]
        if missing:
            # a digest key with no logical-axes entry qualifies with an
            # empty spec — still tp-qualified, and swap's sharding
            # equality check keeps matches safe, but the "re-sharded
            # leaf never matches by digest" guarantee is weakened for
            # these leaves: surface the key drift instead of hiding it
            logger.warning(
                "content digests have no sharding spec for %d leaves "
                "(digest keys drifted from the model's logical axes?): %s",
                len(missing), sorted(missing)[:8],
            )
        return {
            k: qualify_digest(d, f"tp={tp}|{specs.get(k, '')}")
            for k, d in digests.items()
        }

    def _build_runtime(
        self,
        model_id: str,
        checkpoint_dir: str = "",
        staged_params: Optional[Dict[str, Any]] = None,
        warmup: Optional[Any] = None,
        resolved: Optional[tuple] = None,
        staged_digests: Optional[Dict[str, str]] = None,
        staged_quant: Optional[list] = None,
    ) -> _ModelRuntime:
        """Traced wrapper around the cold build: the `with` form ends the
        span (stamping the error) even when the build raises — the
        cold-swap failure path must not leak an open span."""
        with tracing.span(
            "engine.build_runtime",
            model=model_id,
            checkpoint_dir=checkpoint_dir,
            staged=staged_params is not None,
        ):
            return self._build_runtime_impl(
                model_id, checkpoint_dir, staged_params, warmup, resolved,
                staged_digests, staged_quant,
            )

    def _build_runtime_impl(
        self,
        model_id: str,
        checkpoint_dir: str = "",
        staged_params: Optional[Dict[str, Any]] = None,
        warmup: Optional[Any] = None,
        resolved: Optional[tuple] = None,
        staged_digests: Optional[Dict[str, str]] = None,
        staged_quant: Optional[list] = None,
    ) -> _ModelRuntime:
        """Cold-build an awake runtime for `model_id`: config -> tokenizer
        -> params (checkpoint / HF read, or random init) -> engine ->
        sleeper. Pool hits on a slept runtime bypass this entirely;
        `staged_params` (a prefetched host tree) skips the checkpoint read
        and streams straight host -> device. Leaves the build's transfer
        accounting in `_last_build_stats` so a pool-miss swap can report
        its real H2D cost.

        ``warmup`` (a WarmupTask kicked before the transfer started) is
        joined AFTER the weights land and its executables installed into
        the new engine — the build completes with warm weights AND warm
        executables, compile having ridden under the DMA. ``resolved`` is
        an already-computed ``_resolve_model`` tuple (the swap path
        resolves once and shares it with the warmup kick)."""
        args = self.args
        with tracing.stage("start.resolve"):
            if resolved is None:
                resolved = self._resolve_model(model_id)
            model_cfg, eos_token_id, extra_eos, hf_dir, tokenizer = resolved
            # resolving the attention implementation imports the kernels
            # (jax's Pallas: a second of a process's first build)
            engine_cfg = self._engine_cfg_for(
                model_cfg, eos_token_id, extra_eos
            )
        mesh = None
        if args.tensor_parallel_size > 1:
            from ..parallel.mesh import serving_mesh

            mesh = serving_mesh(args.tensor_parallel_size)
        # Build transfer accounting: a pool-miss swap moves the whole
        # incoming model to HBM inside this build, and the swap metrics
        # must say so (h2d seconds/bytes were reported as 0 before).
        build_stats: Dict[str, Any] = {
            "h2d_s": 0.0,
            "bytes_in": 0,
            "buckets_in": 0,
            "overlap_s": 0.0,
            "overlap_frac": 0.0,
        }
        inflight = max(1, getattr(args, "load_inflight_mib", 512)) << 20
        params = None
        #: per-leaf content digests for the new runtime, computed once at
        #: load (or carried through from a prefetch/tier staging) — the
        #: tiered pool's and the delta-swap's weight identity
        digests: Optional[Dict[str, str]] = staged_digests
        t_load0 = time.monotonic()
        # a checkpoint or HF load is the first part of start.weights; the
        # engine's constructor times the rest (placement, or the seeded init)
        loads = bool(checkpoint_dir or hf_dir or staged_params is not None)
        with (
            tracing.stage("start.weights") if loads else tracing.NOOP_SPAN
        ) as weights_stage:
            if checkpoint_dir and staged_params is None:
                from ..models import checkpoint

                ckpt_stats: Dict[str, Any] = {}
                params = checkpoint.load_params(
                    checkpoint_dir, model_cfg, mesh=mesh, stats_out=ckpt_stats
                )
                if self._content_hash:
                    digests = ckpt_stats.get("digests") or None
                # Orbax restores each leaf straight into its device placement:
                # the restore wall IS the cold H2D window (read inseparable)
                build_stats["h2d_s"] = ckpt_stats.get(
                    "restore_s", time.monotonic() - t_load0
                )
                import jax as _jax

                self.costs.observe_transfer(
                    "coldload.h2d",
                    sum(x.nbytes for x in _jax.tree.leaves(params)),
                    build_stats["h2d_s"],
                )
            elif hf_dir or staged_params is not None:
                from ..models import hf as hf_models

                lstats = hf_models.LoadStats()
                if staged_params is not None:
                    # prefetched host weights: no disk read, just the stream in
                    params = hf_models.place_staged_params(
                        staged_params, model_cfg, mesh=mesh,
                        max_inflight_bytes=inflight, stats=lstats,
                    )
                    if staged_quant is not None:
                        # quantized staging (--sleep-quant prefetch): the
                        # placement streamed int8/fp8 payloads (half the PCIe
                        # bytes); expand to serving precision on device,
                        # aligned by flatten order with the staged tree
                        import jax

                        from ..models import quant as transfer_quant

                        leaves, treedef = jax.tree.flatten(params)
                        if len(staged_quant) != len(leaves):
                            # fail LOUD: serving raw int8 payloads as weights
                            # would be silent garbage, never a slow path
                            raise RuntimeError(
                                "quantized staging metadata does not align "
                                f"with the placed tree ({len(staged_quant)} "
                                f"metas vs {len(leaves)} leaves)"
                            )
                        payloads = []
                        for i, meta in enumerate(staged_quant):
                            if meta is None:
                                continue
                            payloads.append(leaves[i])
                            leaves[i] = transfer_quant.dequantize_leaf(
                                leaves[i], meta
                            )
                        params = jax.tree.unflatten(treedef, leaves)
                        params = jax.block_until_ready(params)
                        for p in payloads:
                            p.delete()
                else:
                    # pipelined cold load: parallel shard readers + streaming
                    # placement straight into the serving sharding
                    params = hf_models.load_params(
                        hf_dir, model_cfg, mesh=mesh,
                        workers=getattr(args, "load_workers", 0) or None,
                        max_inflight_bytes=inflight, stats=lstats,
                        want_digests=self._content_hash,
                    )
                    if self._content_hash:
                        digests = dict(lstats.digests) or None
                    for phase, v in (
                        ("read", lstats.read_s),
                        ("convert", lstats.convert_s),
                        ("h2d", lstats.h2d_s),
                        ("total", lstats.total_s),
                    ):
                        ENGINE_COLDLOAD_PHASE_SECONDS.labels(
                            model=model_id, phase=phase
                        ).set(v)
                    ENGINE_COLDLOAD_OVERLAP_FRAC.labels(model=model_id).set(
                        lstats.overlap_frac
                    )
                    weights_stage.set(
                        read_s=round(lstats.read_s, 6),
                        convert_s=round(lstats.convert_s, 6),
                        h2d_s=round(lstats.h2d_s, 6),
                    )
                build_stats.update(
                    h2d_s=lstats.h2d_s,
                    buckets_in=lstats.buckets_h2d,
                    overlap_s=lstats.overlap_s,
                    overlap_frac=lstats.overlap_frac,
                )
                for kind, b, s in lstats.transfer_figures():
                    self.costs.observe_transfer(kind, b, s)
        import jax  # deliberately not module-level: parse-time must not touch a backend

        engine = InferenceEngine(
            engine_cfg,
            params=params,
            mesh=mesh,
            seed=args.seed,
        )
        if not self.is_gang:
            # a serving program's first touch asks the pool's spill
            # directory before it traces (engine.py:_adopt_program); no
            # gang member carries executables (_start_warmup)
            with tracing.stage("start.programs"):
                engine.use_exec_pool(self.exec_pool)
        if params is None:
            # random init lands on device inside engine construction: the
            # whole build window is device-state creation
            build_stats["h2d_s"] = time.monotonic() - t_load0
        build_stats["bytes_in"] = sum(
            x.nbytes
            for x in jax.tree.leaves(
                {"p": engine.params, "kv": engine.pool.as_tuple()}
            )
        )
        if warmup is not None:
            # The transfer is over: join the AOT warmup (it usually
            # finished under the DMA) and hand its executables to the new
            # engine. Signature-checked against the BUILT engine — the
            # warmup resolved its config through the same _resolve_model,
            # but an executable compiled for the wrong eos/shape must
            # never install silently.
            from .exec_pool import exec_signature, mesh_shape

            t_transfer1 = time.monotonic()
            with tracing.stage("start.programs"):
                if warmup.signature == exec_signature(
                    engine.cfg, mesh_shape(engine.mesh)
                ):
                    warmup.install(engine, timeout=600)
                else:
                    warmup.abort()
                    warmup.wait(5)
                    warmup.stats["errors"].append(
                        "signature mismatch with built engine; not installed"
                    )
            build_stats["warmup"] = warmup.overlap_stats(
                window_t1=t_transfer1
            )
            self._last_warmup = warmup
        self._last_build_stats = build_stats
        with tracing.stage("start.sleeper"):
            sleeper = attach_sleep(
                engine,
                bucket_bytes=self._swap_bucket_bytes,
                quant_mode=self._sleep_quant,
                quant_hot_head=self._sleep_quant_hot_head,
                on_transfer=self.costs.observe_transfer,
            )
            if self._sleep_quant != "off" and not self.is_gang:
                # move the quantize/dequantize op compiles off the first
                # actuation's transfer window (and out of the cost oracle's
                # first bandwidth measurements) — the build already pays
                # compile time, this rides with it
                try:
                    sleeper.warm_quant_ops()
                except Exception:  # noqa: BLE001 — warmup is best-effort
                    logger.warning(
                        "transfer-quant op warmup failed", exc_info=True
                    )
            # zero-drain pricing contract (engine/sleep.py peek_state): the
            # oracle's offload peeks exclude the KV pool exactly when an
            # actual offload of this engine will park first
            engine.zero_drain_park = self._zero_drain_parks()
        self.builds_total += 1
        return _ModelRuntime(
            model_id=model_id,
            engine=engine,
            sleeper=sleeper,
            tokenizer=tokenizer,
            hf_dir=hf_dir,
            checkpoint_dir=checkpoint_dir,
            # mesh builds carry shard-qualified digests (idempotent for
            # tier-staged maps that already are): sharded weight
            # identity = content + mesh shape + per-leaf spec
            digests=(
                self._qualify_digests(digests, model_cfg)
                if self._content_hash
                else None
            ),
        )

    def _install_runtime(self, rt: _ModelRuntime) -> None:
        """Point the service at a runtime (initial build or swap). The
        bundle is kept whole in `_runtime` (what a swap-out pools); the
        flat attributes mirror it for the many existing access sites, and
        `args.model` is the single source of the current model name —
        metrics labels, /v1/models, and launcher status all follow it."""
        self._runtime = rt
        self.engine = rt.engine
        self.sleeper = rt.sleeper
        self.tokenizer = rt.tokenizer
        self.hf_dir = rt.hf_dir
        self.checkpoint_dir = rt.checkpoint_dir
        self.args.model = rt.model_id

    def _current_runtime(self) -> _ModelRuntime:
        return self._runtime

    def _retire_model_series(self, previous: str) -> None:
        """Drop the outgoing model's per-model GAUGE label series on swap.
        These gauges are only ever written for the resident model, so
        after a swap the old series would report its last pre-swap value
        forever (a swapped-out model showing phantom queue depth /
        occupancy to the HPA and the fleet rollup). Histograms and
        counters are cumulative and stay. The arrival EWMA restarts too:
        its observations belonged to the outgoing model."""
        for g in (
            ENGINE_QUEUE_DEPTH,
            ENGINE_SLOT_OCCUPANCY,
            ENGINE_KV_USAGE,
            ENGINE_PREFIX_HIT_TOKENS,
            ENGINE_SPEC_PROPOSED,
            ENGINE_SPEC_ACCEPTED,
            ENGINE_ARRIVAL_RATE,
        ):
            try:
                g.remove(previous)
            except KeyError:
                pass
        with self._slo_mu:
            self._arrival = _RateEWMA(self._arrival.tau_s)

    # -- zero-drain actuation: preempt / park / resume (engine/parked.py;
    # docs/perf.md "Zero-drain actuation") -----------------------------------

    def _zero_drain_parks(self) -> bool:
        """True when an actuation on the CURRENT engine preempts-and-
        parks instead of aborting: --zero-drain on, single-process (gang
        bundles would be per-process partial state), and no device
        release (the release path's numpy staging restores full state —
        today's stall-and-resume semantics already hold there)."""
        return (
            self._zero_drain
            and not self.is_gang
            and not getattr(self, "release_on_sleep", False)
        )

    def _park_pageout_bytes(self) -> int:
        """Wire bytes a park of the current engine would page out d2h
        right now — per-page bytes (one pool-layout definition:
        PagePool.page_nbytes) times the live page count
        (engine.parked_page_ids), the SAME arithmetic the park itself
        performs, so predicted and actual park bytes agree exactly."""
        if not self._zero_drain_parks():
            return 0
        from .kv_cache import PagePool

        eng = self.engine
        m = eng.cfg.model
        per_page = PagePool.page_nbytes(
            m.cache_layers,
            eng.cfg.page_size,
            m.num_kv_heads,
            m.head_dim,
            dtype=m.dtype,
        )
        return per_page * len(eng.parked_page_ids())

    def _park_current(self, park_pending: bool) -> Optional[Any]:
        """Preempt the current engine's live work into a ParkedRequests
        bundle: quiesce at the step boundary (caller holds the step
        lock), page the live KV out (fault point ``kvsave.d2h``), detach
        the scheduler, and move the displaced futures (and, on swap, the
        pre-engine pending queue) into the bundle. Returns None — engine
        untouched, caller falls back to the abort path — when the
        page-out failed."""
        eng = self.engine
        t0 = time.monotonic()
        try:
            bundle, finished = eng.park_requests(
                bucket_bytes=self._swap_bucket_bytes
            )
        except Exception:  # noqa: BLE001 — fall back to the abort path
            logger.warning(
                "zero-drain park failed; falling back to the abort path",
                exc_info=True,
            )
            return None
        t1 = time.monotonic()
        # requests a pipelined drain completed during the quiesce: they
        # finished on their own terms and were never preempted
        for req in finished:
            req.done_time = time.monotonic()
            self._observe_finished(req)
            fut = self._futures.pop(req.seq_id, None)
            if fut is not None:
                self._fut_seq.pop(id(fut), None)
                if not fut.done():
                    fut.set_result(req)
        for r in [pr.req for pr in bundle.live] + list(bundle.waiting):
            fut = self._futures.pop(r.seq_id, None)
            if fut is not None:
                self._fut_seq.pop(id(fut), None)
                bundle.futures[r.seq_id] = fut
            # the preempt/park/resume leg accounting: the whole parked
            # window [t0, resume-end] accumulates into preempt_s at
            # resume (or export time, for migrated bundles)
            r._park_t0 = t0
            r._park_t1 = t1
            r._park_pre_token = r.first_token_time is None
            if r.trace is not None:
                r.trace.add(
                    "request.preempt", t0, t1,
                    kv_bytes=bundle.kv_nbytes,
                )
        if park_pending:
            # still-queued HTTP submissions target the outgoing model
            # (validated against its vocab): they park too and re-enter
            # the pending queue on swap-back. pop-one-at-a-time, like
            # the abort path: submit() appends lock-free
            while self._pending:
                bundle.pending.append(self._pending.pop(0))
        if bundle.kv_nbytes:
            ENGINE_KV_PAGEOUT.labels(dir="d2h").inc(bundle.kv_nbytes)
            # the PURE gather window (engine.park_requests stamps it
            # around the d2h alone): quiesce/bookkeeping must not
            # anchor the bandwidth EWMA low
            self.costs.observe_transfer(
                "kvsave.d2h", bundle.kv_nbytes, bundle.pageout_s
            )
        with self._slo_mu:
            self._zd_preempted += bundle.preempted
            self._zd_parked_bytes += bundle.kv_nbytes
        return bundle

    def _abort_parked_bundle(
        self, bundle: Any, model: str, why: str
    ) -> int:
        """A parked bundle can never resume (KV restore failed, parked
        model evicted, L2 escalation dropped the host state): fail every
        displaced future with the existing ``state_loss`` cause — a
        clean abort, never a wedged slot."""
        exc = RuntimeError(why)
        n = 0
        now = time.monotonic()
        for r in [pr.req for pr in bundle.live] + list(bundle.waiting):
            fut = bundle.futures.get(r.seq_id)
            if fut is not None and not fut.done():
                fut.set_exception(exc)
            self._finish_request_trace(
                r, now, aborted=True, outcome="state_loss"
            )
            n += 1
        for entry in bundle.pending:
            fut = entry[3]
            if fut is not None and not fut.done():
                fut.set_exception(exc)
            tr = entry[15]
            if tr is not None:
                tr.finish(
                    entry[14], now, keep=True, outcome="state_loss",
                )
            n += 1
        if n:
            self._count_abort("state_loss", n)
            ENGINE_PREEMPTED.labels(model=model, outcome="aborted").inc(n)
        with self._slo_mu:
            self._zd_aborted += n
            self._zd_parked_bytes -= bundle.kv_nbytes
        return n

    def _resume_parked(self, rt: "_ModelRuntime") -> tuple:
        """Re-seat a runtime's parked bundle into its (awake) engine:
        page the KV back in (fault point ``kvrestore.h2d``), restore
        futures and pending submissions, and let the serving loop
        continue the streams mid-decode. Returns ``(resumed,
        pagein_bytes, seconds, dropped, shortfall)`` — ``dropped``
        counts parked requests whose clients vanished while parked;
        ``shortfall`` is True whenever the page-in moved fewer bytes
        than the bundle predicted (dropped clients, or a failed
        restore), so callers record the actuation UNPRICED instead of
        scoring a false byte-exactness miss. A restore failure is
        rolled back to a clean abort (cause ``state_loss``) with the
        engine healthy and serving — the transactional contract's abort
        leg; re-queued waiting/pending requests (which carried no KV and
        lost nothing) still count ``resumed``, so the documented
        preempted = resumed + aborted balance always closes."""
        from .parked import ParkedResumeFailed

        bundle = rt.parked
        if bundle is None:
            return 0, 0, 0.0, 0, False
        rt.parked = None
        with self._slo_mu:
            self._zd_parked_bytes -= bundle.kv_nbytes
        eng = rt.engine

        def _fut_dead(seq_id: int) -> bool:
            fut = bundle.futures.get(seq_id)
            return fut is not None and fut.done()

        # clients that went away while parked (their futures were
        # cancelled through the abort queue): drop before seating —
        # decoding for a dead client is pure waste
        dead = [pr for pr in bundle.live if _fut_dead(pr.req.seq_id)]
        bundle.live = [
            pr for pr in bundle.live if not _fut_dead(pr.req.seq_id)
        ]
        dead_wait = [r for r in bundle.waiting if _fut_dead(r.seq_id)]
        bundle.waiting = [
            r for r in bundle.waiting if not _fut_dead(r.seq_id)
        ]
        dropped = len(dead) + len(dead_wait)
        if dropped:
            self._count_abort("client", dropped)
            ENGINE_PREEMPTED.labels(
                model=rt.model_id, outcome="aborted"
            ).inc(dropped)
            with self._slo_mu:
                self._zd_aborted += dropped
            now = time.monotonic()
            for r in [pr.req for pr in dead] + dead_wait:
                # tail-keep: a stream the client dropped mid-park is a
                # lifecycle worth reading
                self._finish_request_trace(
                    r, now, aborted=True, outcome="aborted"
                )
        t0 = time.monotonic()
        try:
            n_live, moved = eng.resume_parked(
                bundle, bucket_bytes=self._swap_bucket_bytes
            )
        except ParkedResumeFailed as e:
            # rolled back inside the engine: no slot seated, pages
            # freed, waiting re-queued (they carried no KV). The live
            # requests' KV is gone — abort them cleanly, stay serving.
            exc = RuntimeError(
                f"preempted request aborted: zero-drain KV restore "
                f"failed ({e})"
            )
            nlost = 0
            tloss = time.monotonic()
            for pr in bundle.live:
                fut = bundle.futures.get(pr.req.seq_id)
                if fut is not None and not fut.done():
                    fut.set_exception(exc)
                self._finish_request_trace(
                    pr.req, tloss, aborted=True, outcome="state_loss"
                )
                nlost += 1
            for r in bundle.waiting:
                fut = bundle.futures.get(r.seq_id)
                if fut is not None and not fut.done():
                    self._futures[r.seq_id] = fut
                    self._fut_seq[id(fut)] = r.seq_id
            self._pending.extend(bundle.pending)
            if nlost:
                self._count_abort("state_loss", nlost)
                ENGINE_PREEMPTED.labels(
                    model=rt.model_id, outcome="aborted"
                ).inc(nlost)
            # the re-queued waiting/pending requests carried no KV and
            # continue serving: they RESUMED — without this the
            # documented preempted = resumed + aborted balance
            # (docs/operations.md) would never close after a drill
            requeued = len(bundle.waiting) + len(bundle.pending)
            if requeued:
                ENGINE_PREEMPTED.labels(
                    model=rt.model_id, outcome="resumed"
                ).inc(requeued)
            with self._slo_mu:
                self._zd_aborted += nlost
                self._zd_resumed += requeued
            ENGINE_RECOVERIES.labels(
                path="kvrestore", outcome="rolled_back"
            ).inc()
            self.degraded = (
                f"zero-drain resume aborted {nlost} preempted "
                f"request(s) with state_loss: {e}"
            )
            logger.warning(
                "zero-drain resume failed for %s; %d preempted "
                "request(s) aborted (state_loss)",
                rt.model_id, nlost, exc_info=True,
            )
            self._new_work.set()
            # shortfall=True: the prediction counted the bundle's pages,
            # none moved — the caller must record unpriced
            return 0, 0, time.monotonic() - t0, dropped, True
        t3 = time.monotonic()
        resume_s = t3 - t0
        if moved:
            ENGINE_KV_PAGEOUT.labels(dir="h2d").inc(moved)
            self.costs.observe_transfer("kvrestore.h2d", moved, resume_s)
        for r in [pr.req for pr in bundle.live] + list(bundle.waiting):
            # close the preempt window: parked dwell + the resume
            # transfer accumulate into the request's preempt leg
            pt0 = getattr(r, "_park_t0", None)
            if pt0 is not None:
                r.preempt_s += max(0.0, t3 - pt0)
                if getattr(r, "_park_pre_token", False):
                    r.preempt_pre_token_s += max(0.0, t3 - pt0)
                if r.trace is not None:
                    pt1 = getattr(r, "_park_t1", pt0)
                    r.trace.add("request.park", pt1, t0)
                    r.trace.add(
                        "request.resume", t0, t3, kv_bytes=moved
                    )
                r._park_t0 = None
        for seq_id, fut in bundle.futures.items():
            if not fut.done():
                self._futures[seq_id] = fut
                self._fut_seq[id(fut)] = seq_id
        self._pending.extend(bundle.pending)
        resumed = n_live + len(bundle.waiting) + len(bundle.pending)
        if resumed:
            ENGINE_PREEMPTED.labels(
                model=rt.model_id, outcome="resumed"
            ).inc(resumed)
        with self._slo_mu:
            self._zd_resumed += resumed
        self._new_work.set()
        return resumed, moved, resume_s, dropped, dropped > 0

    def _unpark_current(self, rt: "_ModelRuntime") -> None:
        """Rollback leg of a failed actuation that had already parked:
        put the preempted requests back into live serving (the
        transactional contract's restore leg). The engine's pool is
        rebuilt first when the park's detach is still in effect (a
        pre-transfer rejection); a swap_states rollback already rebuilt
        it through set_state."""
        if rt.parked is None:
            return
        try:
            if rt.engine.kv_detached:
                rt.engine.rebuild_kv_pool()
            self._resume_parked(rt)
        except Exception:  # noqa: BLE001 — _resume_parked aborts cleanly itself
            logger.warning(
                "zero-drain unpark after a failed actuation could not "
                "restore live serving", exc_info=True,
            )

    # -- live request migration: transactional parked-bundle handoff
    # between sibling instances (docs/operations.md "Draining a node
    # without dropping streams") ---------------------------------------------
    #
    # Verb sequence (the launcher drives it):
    #   source GET  /v1/parked/{model}   export_parked  — park + serialize
    #   dest   POST /v1/parked           import_parked  — verify + seat
    #   source POST /v1/parked/release   release_parked — commit + proxy
    #   source POST /v1/parked/abort     abort_migration — local resume
    # The export mints a single-use fence token; the import stores its ack
    # under it (a lost-ack retry replays the SAME ack instead of seating a
    # second copy), and release/abort spend it exactly once — a
    # double-resume is a 409 (MigrationRejected), never a duplicate stream.
    # Client streams only ever resolve through the SOURCE's original
    # futures: after release, per-stream watcher threads proxy the
    # destination's claim views back into them.

    def _migration_identity(self) -> Dict[str, Any]:
        """The model-identity block both ends of a handoff compare:
        name@checkpoint plus an order-independent fingerprint over the
        weight content digests. A runtime with neither digests nor a
        checkpoint directory (random-init dev weights) has no provable
        identity and is refused — KV seated onto different weights
        decodes garbage from valid-looking pages."""
        from . import parked as parked_mod

        rt = self._runtime
        digests = rt.digests if self._content_hash else None
        if not digests and not (rt.checkpoint_dir or ""):
            raise MigrationRejected(
                "no provable weight identity (no content digests and no "
                "checkpoint): migration between random-init engines is "
                "refused"
            )
        return {
            "model": self.args.model,
            "checkpoint_dir": rt.checkpoint_dir or "",
            "weight_fingerprint": (
                parked_mod.weight_fingerprint(digests) if digests else ""
            ),
            "page_size": int(self.args.page_size),
            "vocab_size": int(self.engine.cfg.model.vocab_size),
            "max_model_len": int(self.args.max_model_len or 0),
        }

    def _check_identity(self, theirs: Dict[str, Any]) -> None:
        """Import-side identity gate. Fingerprints are authoritative when
        both sides have them; otherwise the checkpoint path must match
        exactly (same shared filesystem) or the import is refused."""
        mine = self._migration_identity()
        if theirs.get("model") != mine["model"]:
            raise MigrationRejected(
                f"model identity mismatch: bundle is "
                f"{theirs.get('model')!r}, serving {mine['model']!r}"
            )
        fp_t = theirs.get("weight_fingerprint") or ""
        fp_m = mine["weight_fingerprint"]
        if fp_t and fp_m:
            if fp_t != fp_m:
                raise MigrationRejected(
                    "weight fingerprint mismatch: same model name, "
                    "different weights (refusing to seat KV onto foreign "
                    "weights)"
                )
        elif (
            not mine["checkpoint_dir"]
            or (theirs.get("checkpoint_dir") or "") != mine["checkpoint_dir"]
        ):
            raise MigrationRejected(
                "no comparable weight identity (enable --content-hash or "
                "serve both instances from the same checkpoint)"
            )
        if int(theirs.get("page_size", -1)) != mine["page_size"]:
            raise MigrationRejected(
                f"page_size mismatch ({theirs.get('page_size')} != "
                f"{mine['page_size']}): KV pages are not portable"
            )

    def _encode_pending(self, entry: tuple) -> Dict[str, Any]:
        """One parked ``_pending`` submit tuple as a wire spec. The
        future and streaming hook stay behind on the source (the proxy
        leg resolves them); ``submit_time`` is deliberately dropped —
        the importer stamps its own clock."""
        (prompt, max_tokens, temperature, _fut, _on_tokens, top_p,
         stop_seqs, presence, freq, want_alts, want_plp, seed,
         ignore_eos, logit_bias, _submit_t, trace, _stop_watch) = entry
        spec = {
            "prompt": [int(t) for t in prompt],
            "max_tokens": int(max_tokens),
            "temperature": float(temperature),
            "top_p": float(top_p),
            "stop_seqs": [list(s) for s in (stop_seqs or ())],
            "presence_penalty": float(presence),
            "frequency_penalty": float(freq),
            "want_top_logprobs": bool(want_alts),
            "want_prompt_logprobs": bool(want_plp),
            "seed": None if seed is None else int(seed),
            "ignore_eos": bool(ignore_eos),
            "logit_bias": {
                str(t): float(v) for t, v in (logit_bias or {}).items()
            },
        }
        if trace is not None:
            ctx = trace.context()
            spec["trace"] = {
                "trace_id": ctx.trace_id, "span_id": ctx.span_id,
            }
        return spec

    def _decode_pending(self, spec: Dict[str, Any], fut: Any) -> tuple:
        """Rebuild a local ``_pending`` entry from a wire spec with a
        fresh destination-side future (the importer's claim record holds
        it; the source's original future is resolved by the proxy)."""
        tr = spec.get("trace")
        trace = None
        if (
            isinstance(tr, dict)
            and tr.get("trace_id")
            and tracing.enabled()
        ):
            # adopt the origin trace: destination spans join the SAME
            # trace_id, parented on the source's lifecycle root.
            # Migrated-in work is always retained (migration forensics).
            trace = tracing.RequestTrace(
                sampled=True,
                parent=tracing.SpanContext(
                    str(tr["trace_id"]), str(tr.get("span_id", ""))
                ),
            )
        return (
            [int(t) for t in spec["prompt"]],
            int(spec["max_tokens"]),
            float(spec["temperature"]),
            fut,
            None,
            float(spec["top_p"]),
            tuple(
                tuple(int(t) for t in s) for s in spec.get("stop_seqs", ())
            ),
            float(spec["presence_penalty"]),
            float(spec["frequency_penalty"]),
            bool(spec["want_top_logprobs"]),
            bool(spec["want_prompt_logprobs"]),
            None if spec["seed"] is None else int(spec["seed"]),
            bool(spec["ignore_eos"]),
            {int(t): float(v) for t, v in spec.get("logit_bias", {}).items()},
            time.monotonic(),
            trace,
            None,
        )

    def price_migrate(self) -> Dict[str, Any]:
        """Predicted cost of exporting this engine's live work to a
        sibling: live KV pages (the same arithmetic the park performs)
        plus the per-live-request scheduler rows, priced through the
        ``migrate.export`` bandwidth EWMA. What /v1/costs exposes so the
        launcher can pick cheap drain moments."""
        eng = self.engine
        park = self._park_pageout_bytes()
        live = sum(
            1 for r in eng._slots
            if r is not None and not r.done and not r.prefilling
        )
        # counts_row is [vocab] int32, key_data [2] uint32 — exact by
        # construction, like the KV figure (park_requests stamps
        # bundle.nbytes from the same quantities)
        meta = live * (int(eng.cfg.model.vocab_size) * 4 + 8)
        predicted = park + meta
        s, measured = self.costs.bandwidths.seconds_for(
            "migrate.export", predicted
        )
        return {
            "kind": "migrate",
            "model": self.args.model,
            "enabled": self._zero_drain_parks(),
            "predicted_bytes": predicted,
            "predicted_kv_bytes": park,
            "predicted_s": round(s, 6),
            "measured": measured,
            "requests": (
                live + len(eng._waiting) + len(self._pending)
            ),
        }

    def export_parked(self, model: str) -> Dict[str, Any]:
        """GET /v1/parked/{model}: preempt-and-park every live stream
        and serialize the bundle for a sibling. On success the engine is
        ALREADY serving again (fresh pool) — new arrivals never wait on
        the handoff — and the bundle is retained under a fence token
        until release/abort. Fault point ``migrate.export`` fires after
        the park: its drilled recovery is a LOCAL resume (the bundle
        never left this process, so nothing can be lost)."""
        from . import parked as parked_mod

        if model != self.args.model:
            raise MigrationRejected(
                f"model {model!r} is not the serving base "
                f"(serving {self.args.model!r})"
            )
        if self.sleeper.is_sleeping:
            raise MigrationRejected(
                "instance is sleeping; wake it before migrating"
            )
        from .engine import SlotStateUnsupported, refuse_slot_state

        try:
            refuse_slot_state(self.engine.cfg.model, "a live migration")
        except SlotStateUnsupported as e:
            raise MigrationRejected(str(e)) from e
        if not self._zero_drain_parks():
            raise MigrationRejected(
                "zero-drain parking unavailable (--zero-drain off, gang "
                "serving, or --release-on-sleep): nothing can be parked "
                "for migration"
            )
        if self._migration is not None:
            raise MigrationRejected(
                "a migration is already in flight "
                f"(fence {self._migration['token']})"
            )
        identity = self._migration_identity()
        try:
            pred: Optional[Dict[str, Any]] = self.price_migrate()
        except Exception:  # noqa: BLE001 — pricing must never block the verb
            pred = None
        t0 = time.monotonic()
        with tracing.span("migrate.export", model=model) as sp:
            with self._admin_lock():
                bundle = self._park_current(park_pending=True)
                if bundle is None:
                    raise MigrationFailed(
                        "zero-drain park failed; nothing was displaced "
                        "(streams still live)"
                    )
                try:
                    faults.fire("migrate.export")
                    doc = parked_mod.encode_wire(
                        bundle, identity,
                        chunk_bytes=self._swap_bucket_bytes,
                    )
                    import jax
                    import numpy as np

                    eng = self.engine
                    for spec in doc["requests"]["waiting"]:
                        if spec.get("seed") is None:
                            # pin the exact initial key THIS engine's
                            # admission would derive from (seed, seq_id):
                            # both differ on the importer
                            k = jax.random.fold_in(
                                jax.random.key(eng._seed + 1),
                                int(spec["seq_id"]),
                            )
                            spec["rng_key_data"] = parked_mod.pack_array(
                                np.asarray(jax.random.key_data(k))
                            )
                    doc["requests"]["pending"] = [
                        self._encode_pending(e) for e in bundle.pending
                    ]
                except Exception as e:  # noqa: BLE001 — any export-leg failure resumes locally
                    rt = self._runtime
                    rt.parked = bundle
                    self._unpark_current(rt)
                    with self._slo_mu:
                        self._mig["resumed_local"] += 1
                    ENGINE_MIGRATIONS.labels(
                        role="source", outcome="resumed_local"
                    ).inc()
                    self._record_actuation(
                        "migrate", model, trigger="export", tier="wire",
                        pred=None, actual_bytes=0,
                        actual_s=time.monotonic() - t0,
                        outcome="resumed_local",
                        extra={"error": f"{type(e).__name__}: {e}"},
                    )
                    raise MigrationFailed(
                        f"export failed ({e}); streams resumed locally"
                    ) from e
                import uuid

                self._migration_gen += 1
                token = (
                    f"mig-{self._migration_gen}-{uuid.uuid4().hex[:12]}"
                )
                doc["fence"] = {
                    "token": token,
                    "gen": self._migration_gen,
                    "source_model": model,
                }
                self._migration = {
                    "token": token,
                    "bundle": bundle,
                    "model": model,
                    "pred": pred,
                    "t0": t0,
                    "nbytes": int(doc["nbytes"]),
                    "requests": bundle.preempted,
                }
                # the handoff spans separate HTTP round-trips: rebuild
                # the pool NOW so new arrivals serve during the window —
                # the abort leg's local resume re-seats into it, exactly
                # like _unpark_current after a failed swap
                self.engine.rebuild_kv_pool()
            encode_s = time.monotonic() - t0
            nbytes = int(doc["nbytes"])
            if nbytes:
                self.costs.observe_transfer(
                    "migrate.export", nbytes, encode_s
                )
            ENGINE_MIGRATE_BYTES.labels(dir="export").inc(nbytes)
            with self._slo_mu:
                self._mig["exported"] += 1
                self._mig["bytes_out"] += nbytes
            sp.set(
                nbytes=nbytes, requests=bundle.preempted, fence=token
            )
            self._new_work.set()
            return doc

    def import_parked(self, doc: Dict[str, Any]) -> Dict[str, Any]:
        """POST /v1/parked: verify and seat a sibling's exported bundle.
        Everything is checked BEFORE any engine state moves (wire
        version, every KV chunk digest, weight identity, slot/page
        capacity) so a refusal leaves the destination untouched; a seat
        failure strips the foreign requests back out (clean rollback).
        The ack is stored under the fence token BEFORE the ``migrate.ack``
        fault point fires, so a lost-ack retry replays the SAME ack
        instead of seating a duplicate."""
        from . import parked as parked_mod
        from .engine import Request
        from .kv_cache import PageAllocator

        fence = doc.get("fence") or {}
        token = str(fence.get("token") or "")
        if not token:
            raise ValueError("parked import without a fence token")
        with self._slo_mu:
            replay = self._import_acks.get(token)
        if replay is not None:
            # idempotent lost-ack retry: the seat already happened
            return dict(replay)
        if token in self._spent_fences:
            raise MigrationRejected(
                f"fence token {token!r} already spent "
                "(double-resume refused)"
            )
        if self.sleeper.is_sleeping:
            raise MigrationRejected(
                "instance is sleeping; wake it before importing"
            )
        self._check_identity(doc.get("identity") or {})
        t0 = time.monotonic()
        with tracing.span(
            "migrate.import", model=self.args.model, fence=token
        ) as sp:
            # decode verifies every chunk digest (ValueError -> 400)
            bundle, pending_specs = parked_mod.decode_wire(doc, Request)
            try:
                faults.fire("migrate.import")
            except faults.FaultError as e:
                with self._slo_mu:
                    self._mig["rolled_back"] += 1
                ENGINE_MIGRATIONS.labels(
                    role="destination", outcome="rolled_back"
                ).inc()
                raise MigrationFailed(
                    f"import failed before seating ({e}); destination "
                    "clean"
                ) from e
            import uuid

            with self._admin_lock():
                eng = self.engine
                if eng.kv_detached:
                    raise MigrationRejected(
                        "KV pool detached (mid-actuation); retry after "
                        "it settles"
                    )
                free_slots = sum(1 for s in eng._slots if s is None)
                if len(bundle.live) > free_slots:
                    raise MigrationRejected(
                        f"no capacity: {len(bundle.live)} live streams "
                        f"need slots, {free_slots} free"
                    )
                # conservative (sharing-blind) page bound: resume
                # allocates each live request's FULL budget
                need_pages = sum(
                    PageAllocator.pages_needed(
                        len(pr.req.prompt) + pr.req.max_new_tokens,
                        self.args.page_size,
                    )
                    for pr in bundle.live
                )
                if need_pages > eng.allocator.available:
                    raise MigrationRejected(
                        f"no capacity: bundle needs up to {need_pages} "
                        f"KV pages, {eng.allocator.available} free"
                    )
                # re-key into this engine's id space; the ack's claims
                # map (source seq_id -> claim id) lets the source proxy
                # each stream back to its original client
                claims: Dict[str, str] = {}
                recs: List[tuple] = []
                for pr in bundle.live:
                    old = int(pr.req.seq_id)
                    pr.req.seq_id = eng.new_seq_id()
                    cid = uuid.uuid4().hex
                    claims[str(old)] = cid
                    recs.append((cid, pr.req))
                for r in bundle.waiting:
                    old = int(r.seq_id)
                    r.seq_id = eng.new_seq_id()
                    cid = uuid.uuid4().hex
                    claims[str(old)] = cid
                    recs.append((cid, r))
                waiting_snapshot = list(bundle.waiting)
                try:
                    n_live, moved = eng.resume_parked(
                        bundle, bucket_bytes=self._swap_bucket_bytes
                    )
                except parked_mod.ParkedResumeFailed as e:
                    # the engine re-queued bundle.waiting — right for a
                    # LOCAL resume, wrong here: these are foreign
                    # requests the source still owns. Strip them so the
                    # rollback really is clean.
                    drop = {id(r) for r in waiting_snapshot}
                    eng._waiting = [
                        r for r in eng._waiting if id(r) not in drop
                    ]
                    with self._slo_mu:
                        self._mig["rolled_back"] += 1
                    ENGINE_MIGRATIONS.labels(
                        role="destination", outcome="rolled_back"
                    ).inc()
                    raise MigrationFailed(
                        f"import seat failed ({e}); destination rolled "
                        "back clean"
                    ) from e
                t_seat = time.monotonic()
                for cid, r in recs:
                    fut: concurrent.futures.Future = (
                        concurrent.futures.Future()
                    )
                    self._futures[r.seq_id] = fut
                    self._fut_seq[id(fut)] = r.seq_id
                    self._imported_claims[cid] = {"req": r, "fut": fut}
                    if r.trace_parent and tracing.enabled():
                        # join the origin trace: same trace_id, spans
                        # parented on the source's lifecycle root.
                        # Always retained — a migrated request's spans
                        # are read from both sides' /v1/traces under one
                        # trace_id (tests/test_reqtrace.py).
                        r.trace = tracing.RequestTrace(
                            sampled=True,
                            parent=tracing.SpanContext(
                                str(r.trace_parent["trace_id"]),
                                str(r.trace_parent.get("span_id", "")),
                            ),
                        )
                        r.trace.add(
                            "request.resume", t0, t_seat,
                            migrated=True, fence=token,
                        )
                for i, spec in enumerate(pending_specs):
                    fut = concurrent.futures.Future()
                    cid = uuid.uuid4().hex
                    claims[f"p{i}"] = cid
                    self._imported_claims[cid] = {"req": None, "fut": fut}
                    self._pending.append(self._decode_pending(spec, fut))
            if moved:
                # kvrestore.h2d's bandwidth EWMA deliberately NOT
                # observed here: this window includes decode+verify, and
                # that EWMA only ever sees pure transfer windows
                ENGINE_KV_PAGEOUT.labels(dir="h2d").inc(moved)
            import_s = time.monotonic() - t0
            nbytes = int(doc.get("nbytes", 0))
            if nbytes:
                self.costs.observe_transfer(
                    "migrate.import", nbytes, import_s
                )
            ENGINE_MIGRATE_BYTES.labels(dir="import").inc(nbytes)
            n_req = len(recs) + len(pending_specs)
            with self._slo_mu:
                self._mig["imported"] += 1
                self._mig["bytes_in"] += nbytes
                self._mig["requests_in"] += n_req
            ENGINE_MIGRATIONS.labels(
                role="destination", outcome="imported"
            ).inc()
            self._record_actuation(
                "migrate", self.args.model, trigger="import",
                tier="wire", pred=None, actual_bytes=nbytes,
                actual_s=import_s, outcome="imported",
                extra={"requests": n_req, "fence": token},
            )
            ack = {
                "ok": True,
                "fence_token": token,
                "model": self.args.model,
                "seated": n_live,
                "waiting": len(waiting_snapshot),
                "pending": len(pending_specs),
                "requests": n_req,
                "kv_bytes": moved,
                "claims": claims,
            }
            with self._slo_mu:
                self._import_acks[token] = dict(ack)
            self._new_work.set()
            sp.set(nbytes=nbytes, requests=n_req, seated=n_live)
            try:
                faults.fire("migrate.ack")
            except faults.FaultError as e:
                # the seat SUCCEEDED and the stored ack replays on the
                # retry — only the response is lost (the drilled
                # lost-ack leg)
                raise MigrationFailed(
                    f"import ack lost ({e}); retry the import (fenced, "
                    "idempotent)"
                ) from e
            return ack

    def release_parked(
        self,
        token: str,
        dest: str = "",
        claims: Optional[Dict[str, str]] = None,
    ) -> Dict[str, Any]:
        """POST /v1/parked/release: the destination acked the import —
        commit. Spends the fence (a second release, or an abort after
        this, is a 409) and hands every displaced stream to a watcher
        thread that proxies the destination's claim back into the
        ORIGINAL future and streaming hook: the client never reconnects,
        and exactly-once holds because only the source resolves these
        futures."""
        mig = self._migration
        if mig is None or mig["token"] != token:
            raise MigrationRejected(
                f"fence token {token!r} is not the in-flight migration "
                "(spent or unknown)"
            )
        self._migration = None
        self._spent_fences.add(token)
        bundle = mig["bundle"]
        claims = dict(claims or {})
        model = mig["model"]
        with tracing.span("migrate.release", model=model, fence=token):
            watchers = 0
            lost = 0
            gone = 0
            gone_claims: List[str] = []
            now = time.monotonic()
            for r in [pr.req for pr in bundle.live] + list(bundle.waiting):
                fut = bundle.futures.get(r.seq_id)
                cid = claims.get(str(int(r.seq_id)))
                if fut is None or fut.done():
                    # client dropped while the bundle was in flight: ONE
                    # abort (reason=client) HERE, and the destination is
                    # told to abort its claim so it both stops decoding
                    # and counts its own single client abort
                    gone += 1
                    if cid:
                        gone_claims.append(cid)
                    self._finish_migrate_trace(
                        r, mig["t0"], now, dest, outcome="aborted"
                    )
                    continue
                if not cid:
                    fut.set_exception(RuntimeError(
                        "migrated stream lost: destination acked no "
                        "claim for it"
                    ))
                    self._count_abort("state_loss")
                    lost += 1
                    self._finish_migrate_trace(
                        r, mig["t0"], now, dest, outcome="state_loss"
                    )
                    continue
                self._finish_migrate_trace(
                    r, mig["t0"], now, dest, outcome="migrated"
                )
                self._start_claim_watcher(dest, cid, r, fut)
                watchers += 1
            for i, entry in enumerate(bundle.pending):
                fut = entry[3]
                cid = claims.get(f"p{i}")
                tr = entry[15]
                if fut is None or fut.done():
                    gone += 1
                    if cid:
                        gone_claims.append(cid)
                    if tr is not None:
                        tr.add(
                            "request.migrate", mig["t0"], now,
                            dest=dest or "", outcome="aborted",
                        )
                        tr.finish(
                            entry[14], now, keep=True, outcome="aborted"
                        )
                    continue
                if not cid:
                    fut.set_exception(RuntimeError(
                        "migrated submission lost: destination acked no "
                        "claim for it"
                    ))
                    self._count_abort("state_loss")
                    lost += 1
                    if tr is not None:
                        tr.add(
                            "request.migrate", mig["t0"], now,
                            dest=dest or "", outcome="state_loss",
                        )
                        tr.finish(
                            entry[14], now, keep=True,
                            outcome="state_loss",
                        )
                    continue
                if tr is not None:
                    tr.add(
                        "request.migrate", mig["t0"], now,
                        dest=dest or "", outcome="migrated",
                    )
                    tr.finish(
                        entry[14], now, keep=True, outcome="migrated"
                    )
                self._start_claim_watcher(
                    dest, cid, self._pending_proxy_req(entry), fut
                )
                watchers += 1
            n = bundle.preempted
            migrated = n - lost - gone
            if gone:
                # the dropped-client invariant (tests pin it): exactly
                # one reason=client abort and one outcome=aborted on the
                # source for a migrated-then-disconnected stream
                self._count_abort("client", gone)
            if lost or gone:
                ENGINE_PREEMPTED.labels(
                    model=model, outcome="aborted"
                ).inc(lost + gone)
            if migrated:
                ENGINE_PREEMPTED.labels(
                    model=model, outcome="migrated"
                ).inc(migrated)
            with self._slo_mu:
                self._zd_migrated += migrated
                self._zd_aborted += lost + gone
                self._zd_parked_bytes -= bundle.kv_nbytes
                self._mig["committed"] += 1
                self._mig["requests_out"] += migrated
            if gone_claims:
                self._abort_claims_async(dest, gone_claims)
            ENGINE_MIGRATIONS.labels(
                role="source", outcome="committed"
            ).inc()
            self._record_actuation(
                "migrate", model, trigger="migrate", tier="wire",
                pred=mig["pred"], actual_bytes=mig["nbytes"],
                actual_s=time.monotonic() - mig["t0"],
                outcome="committed",
                extra={
                    "requests": n,
                    "proxied": watchers,
                    "fence": token,
                    "dest": dest or None,
                },
            )
            return {
                "ok": True,
                "fence_token": token,
                "model": model,
                "migrated": migrated,
                "proxied": watchers,
            }

    def abort_migration(self, token: str) -> Dict[str, Any]:
        """POST /v1/parked/abort: the handoff failed after export (the
        import errored twice, or the destination is gone) — spend the
        fence and resume the bundle LOCALLY, the drilled recovery for
        every single-fault case. Only an explicit double fault (the
        local KV page-in failing too) degrades to the existing
        ``state_loss`` abort."""
        mig = self._migration
        if mig is None or mig["token"] != token:
            raise MigrationRejected(
                f"fence token {token!r} is not the in-flight migration "
                "(spent or unknown)"
            )
        self._migration = None
        self._spent_fences.add(token)
        bundle = mig["bundle"]
        model = mig["model"]
        resumed, moved, seconds, dropped = 0, 0, 0.0, 0
        shortfall = True
        with tracing.span("migrate.abort", model=model, fence=token):
            rt = self._runtime
            with self._admin_lock():
                rt.parked = bundle
                try:
                    if rt.engine.kv_detached:
                        rt.engine.rebuild_kv_pool()
                except Exception:  # noqa: BLE001 — double fault: abort below
                    logger.warning(
                        "KV pool rebuild failed while aborting a "
                        "migration", exc_info=True,
                    )
                if rt.parked is not None and not rt.engine.kv_detached:
                    resumed, moved, seconds, dropped, shortfall = (
                        self._resume_parked(rt)
                    )
                if rt.parked is not None:
                    b, rt.parked = rt.parked, None
                    self._abort_parked_bundle(
                        b, model,
                        "preempted request aborted: migration aborted "
                        "and the KV pool could not be rebuilt "
                        "(state_loss)",
                    )
            # _resume_parked's failure leg returns resumed=0 with
            # shortfall set; a live-carrying bundle that hit it lost KV
            outcome = "resumed_local"
            if shortfall and resumed == 0 and mig["requests"] > dropped:
                outcome = "state_loss"
            with self._slo_mu:
                self._mig[outcome] += 1
            ENGINE_MIGRATIONS.labels(role="source", outcome=outcome).inc()
            self._record_actuation(
                "migrate", model, trigger="abort", tier="wire",
                pred=None, actual_bytes=moved, actual_s=seconds,
                outcome=outcome,
                extra={
                    "resumed": resumed,
                    "dropped": dropped,
                    "fence": token,
                },
            )
            return {
                "ok": outcome == "resumed_local",
                "outcome": outcome,
                "fence_token": token,
                "model": model,
                "resumed": resumed,
            }

    def claim_view(
        self, claim_id: str, wait_s: float = 0.0, have: int = -1
    ) -> Dict[str, Any]:
        """GET /v1/parked/claims/{id}: the destination's view of one
        migrated-in stream. Long-poll flavored: blocks up to ``wait_s``
        until the stream finishes or more than ``have`` holdback-safe
        tokens exist. Mid-flight snapshots exclude tokens a stop
        sequence might yet strip (engine._stream's exact rule), so the
        source proxy never streams content the engine itself would have
        held back."""
        from .engine import _stop_holdback

        rec = self._imported_claims.get(claim_id)
        if rec is None:
            raise ValueError(f"unknown claim {claim_id!r}")
        deadline = time.monotonic() + max(0.0, min(float(wait_s), 30.0))
        while True:
            fut = rec["fut"]
            if fut.done():
                from . import parked as parked_mod

                try:
                    req = fut.result()
                except Exception as e:  # noqa: BLE001 — surfaced to the proxy
                    return {
                        "done": True,
                        "error": f"{type(e).__name__}: {e}",
                    }
                return {
                    "done": True,
                    "request": parked_mod.encode_request(req),
                    "finish_reason": req.finish_reason,
                }
            req = rec.get("req")
            if req is None:
                # a parked PENDING submission: the Request exists only
                # after the serving loop admits it
                seq = self._fut_seq.get(id(fut))
                if seq is not None:
                    req = self._find_live_request(seq)
                    if req is not None:
                        rec["req"] = req
            toks: List[int] = []
            if req is not None:
                out = list(req.out_tokens)
                hold = _stop_holdback(out, req.stop_seqs)
                toks = out[: len(out) - hold] if hold else out
            if len(toks) > have or time.monotonic() >= deadline:
                return {"done": False, "tokens": [int(t) for t in toks]}
            time.sleep(0.02)

    def abort_claim(self, claim_id: str) -> Dict[str, Any]:
        """DELETE /v1/parked/claims/{id}: the source's proxy learned its
        client went away — stop generating for the migrated-in stream
        here too. Funnels through the normal abort choke point so this
        instance records its own single client abort; the source records
        the matching one when it reaps the dropped future."""
        rec = self._imported_claims.pop(claim_id, None)
        if rec is None:
            raise ValueError(f"unknown claim {claim_id!r}")
        fut = rec["fut"]
        aborted = not fut.done()
        if aborted:
            self.abort(fut)
        return {"ok": True, "claim_id": claim_id, "aborted": aborted}

    def _find_live_request(self, seq_id: int):
        eng = self.engine
        for r in eng._slots:
            if r is not None and r.seq_id == seq_id:
                return r
        for r in eng._waiting:
            if r.seq_id == seq_id:
                return r
        return None

    def _pending_proxy_req(self, entry: tuple):
        """A host-side Request stand-in for a parked PENDING
        submission's proxy leg: the watcher streams into it and resolves
        the original future with it — field-compatible with what the
        local serving loop would have resolved."""
        from .engine import Request

        spec = self._encode_pending(entry)
        req = Request(
            seq_id=-1,
            prompt=[int(t) for t in spec["prompt"]],
            max_new_tokens=int(spec["max_tokens"]),
            temperature=float(spec["temperature"]),
        )
        req.top_p = float(spec["top_p"])
        req.stop_seqs = tuple(
            tuple(int(t) for t in s) for s in spec["stop_seqs"]
        )
        req.presence_penalty = float(spec["presence_penalty"])
        req.frequency_penalty = float(spec["frequency_penalty"])
        req.want_top_logprobs = bool(spec["want_top_logprobs"])
        req.want_prompt_logprobs = bool(spec["want_prompt_logprobs"])
        req.seed = spec["seed"]
        req.ignore_eos = bool(spec["ignore_eos"])
        req.logit_bias = {
            int(t): float(v) for t, v in spec["logit_bias"].items()
        }
        req.on_tokens = entry[4]
        req.submit_time = entry[14]
        return req

    def _claim_fetch(
        self, dest: str, claim_id: str, have: int, wait_s: float
    ) -> Dict[str, Any]:
        """Fetch one claim view from the destination engine. A seam:
        tests inject an in-process fetcher here; the default speaks the
        engine HTTP API."""
        import urllib.request

        url = (
            f"{dest.rstrip('/')}/v1/parked/claims/{claim_id}"
            f"?have={int(have)}&wait_s={wait_s:g}"
        )
        with urllib.request.urlopen(url, timeout=wait_s + 10.0) as resp:
            return json.loads(resp.read().decode())

    def _claim_abort(self, dest: str, claim_id: str) -> None:
        """Tell the destination a migrated stream's client went away
        (DELETE its claim). A seam like _claim_fetch: tests inject an
        in-process caller; the default speaks the engine HTTP API."""
        import urllib.request

        url = f"{dest.rstrip('/')}/v1/parked/claims/{claim_id}"
        urllib.request.urlopen(
            urllib.request.Request(url, method="DELETE"), timeout=10.0
        ).close()

    def _abort_claims_async(self, dest: str, claim_ids: List[str]) -> None:
        """Best-effort destination claim aborts off-thread (release and
        _drain_aborts run under locks; a dead destination must not wedge
        them). Failure is tolerable — the destination merely decodes a
        dead stream to completion and counts it finished."""
        if not dest or not claim_ids:
            return

        def run() -> None:
            for cid in claim_ids:
                try:
                    self._claim_abort(dest, cid)
                except Exception:  # noqa: BLE001 — best-effort
                    logger.debug(
                        "claim abort %s on %s failed", cid, dest,
                        exc_info=True,
                    )

        threading.Thread(
            target=run, name="migrate-claim-abort", daemon=True
        ).start()

    def _start_claim_watcher(
        self, dest: str, claim_id: str, req: Any, fut: Any
    ) -> None:
        # register BEFORE the thread starts: a client disconnect racing
        # the watcher must find the proxy record in _drain_aborts
        self._proxied[id(fut)] = {"dest": dest, "claim": claim_id}
        threading.Thread(
            target=self._watch_claim,
            args=(dest, claim_id, req, fut),
            name=f"migrate-claim-{claim_id[:8]}",
            daemon=True,
        ).start()

    def _proxy_stream(self, req: Any, done: bool) -> None:
        """Deliver proxied tokens through the original streaming hook
        with engine._stream's exact contract: one call for what a claim
        view brought, ``req.done`` True only on the delivery that holds
        the final token (the SSE writer keys its terminator on it). Claim
        snapshots are already holdback-safe."""
        req.done = done
        if req.on_tokens is None:
            req.streamed = len(req.out_tokens)
        elif len(req.out_tokens) > req.streamed:
            deliver_tokens(req, len(req.out_tokens))

    def _watch_claim(
        self, dest: str, claim_id: str, req: Any, fut: Any
    ) -> None:
        """Source-side proxy for one migrated stream: poll the
        destination's claim, forward newly-safe tokens through the
        original ``on_tokens`` hook, and resolve the original future with
        the finished request. Destination-side aborts and a destination
        that stays unreachable surface as the existing ``state_loss``
        abort — never a silent hang."""
        try:
            self._watch_claim_inner(dest, claim_id, req, fut)
        finally:
            # idempotent: _drain_aborts may have popped it already (and
            # counted the client abort); this keeps the registry clean
            # on the watcher's own terminal paths
            self._proxied.pop(id(fut), None)

    def _watch_claim_inner(
        self, dest: str, claim_id: str, req: Any, fut: Any
    ) -> None:
        backoff = 0.1
        first_fail: Optional[float] = None
        while not self._stop:
            if fut.done():
                return  # client went away; nothing left to proxy
            try:
                view = self._claim_fetch(
                    dest, claim_id, len(req.out_tokens), 5.0
                )
            except Exception as e:  # noqa: BLE001 — network/dest failures retry
                now = time.monotonic()
                if first_fail is None:
                    first_fail = now
                if now - first_fail > 60.0:
                    if not fut.done():
                        fut.set_exception(RuntimeError(
                            "migrated stream lost: destination "
                            f"unreachable ({e})"
                        ))
                        self._count_abort("state_loss")
                    return
                time.sleep(backoff)
                backoff = min(2.0, backoff * 2)
                continue
            first_fail = None
            backoff = 0.1
            if view.get("done"):
                err = view.get("error")
                if err:
                    if not fut.done():
                        fut.set_exception(RuntimeError(
                            "migrated stream aborted on the "
                            f"destination: {err}"
                        ))
                        self._count_abort("state_loss")
                    return
                from . import parked as parked_mod

                final = parked_mod.decode_request(
                    view["request"], type(req)
                )
                req.out_tokens = final.out_tokens
                req.out_logprobs = final.out_logprobs
                req.out_top_logprobs = final.out_top_logprobs
                req.prompt_logprobs = final.prompt_logprobs
                req.pos = final.pos
                req.cached_tokens = final.cached_tokens
                req.stop_requested = final.stop_requested
                req.finish_reason = view.get("finish_reason", "")
                req.done_time = time.monotonic()
                self._proxy_stream(req, done=True)
                if not fut.done():
                    fut.set_result(req)
                return
            toks = view.get("tokens") or []
            if len(toks) > len(req.out_tokens):
                req.out_tokens = [int(t) for t in toks]
                self._proxy_stream(req, done=False)

    # -- actuation cost oracle (GET /v1/costs; docs/operations.md
    # "Pricing an actuation") ------------------------------------------------

    def _model_cfg_cheap(self, model_id: str):
        """Model config for `model_id` WITHOUT the tokenizer load
        ``_resolve_model`` pays: pricing every candidate in one
        /v1/costs call must stay cheap (config.json read for hf:,
        factory call for named configs)."""
        if model_id.startswith("hf:"):
            from ..models import hf as hf_models

            return hf_models.config_from_hf(
                model_id[3:], quantization=self.args.quantization or ""
            )
        if model_id not in MODEL_CONFIGS:
            raise ValueError(f"unknown model {model_id!r}")
        model_cfg = MODEL_CONFIGS[model_id]()
        if (
            self.args.quantization
            and model_cfg.quantization != self.args.quantization
        ):
            import dataclasses

            model_cfg = dataclasses.replace(
                model_cfg, quantization=self.args.quantization
            )
        return model_cfg

    def _kv_pool_nbytes(self, model_cfg) -> int:
        """Device bytes of the sequence state a runtime for `model_cfg`
        creates — counted in a cold build's ``bytes_in``, so the
        oracle's cold predictions must count it identically (the layouts
        live in ONE place: PagePool.estimate_nbytes for the pages,
        kv_cache.recurrent_nbytes for a linear-attention model's recurrent
        state; a latent-attention model's pages are one array of its stored
        row's width). A windowed model's rings are not counted (ROADMAP
        D4)."""
        import jax.numpy as jnp

        from .kv_cache import PagePool, recurrent_nbytes

        pages = PagePool.estimate_nbytes(
            model_cfg.cache_layers,
            self.args.num_pages,
            self.args.page_size,
            model_cfg.num_kv_heads,
            model_cfg.head_dim,
            dtype=model_cfg.dtype,
            latent_width=(llama.latent_cache(model_cfg) or (0, 0))[1],
        )
        return pages + recurrent_nbytes(
            llama.recurrent_state(model_cfg), self.args.max_batch,
            jnp.dtype(model_cfg.dtype).itemsize,
        )

    def _offload_wire_bytes(self) -> int:
        """Wire bytes a level-1 offload of the CURRENT runtime would
        move d2h — payload bytes for --sleep-quant-eligible leaves,
        priced from shapes alone (models/quant.payload_nbytes)."""
        import jax

        from ..models import quant as transfer_quant

        state = self.sleeper._peek_state()
        leaves = jax.tree.leaves(state)
        plan = self.sleeper._quant_plan(state)
        if not plan:
            return sum(x.nbytes for x in leaves)
        mode = self.sleeper.quant_mode
        return sum(
            transfer_quant.payload_nbytes(x.shape, mode) if f else x.nbytes
            for x, f in zip(leaves, plan)
        )

    def price_swap(
        self,
        model: str,
        checkpoint_dir: str = "",
        _offload_wire: Optional[int] = None,
        _exec_desc: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Pre-transfer pricing of a hot-swap to `model`: predicted wire
        bytes (exact-by-construction for pool-hit delta/quant swaps —
        the dry-run shares ``swap_states``'s planner; shape/manifest
        estimates for the cold tiers) and predicted seconds (bytes ÷
        the measured per-kind bandwidth EWMAs). Read-only and
        lock-free: nothing is consumed, nothing moves — concurrent
        actuations make the answer advisory, never wrong-state."""
        if model.startswith("hf:"):
            if not model[3:]:
                raise ValueError("swap model hf: needs a directory path")
        elif model not in MODEL_CONFIGS:
            raise ValueError(
                f"unknown model {model!r}; known: "
                f"{sorted(MODEL_CONFIGS)} or hf:<model-dir>"
            )
        book = self.costs.bandwidths
        out: Dict[str, Any] = {
            "kind": "swap",
            "model": model,
            "checkpoint_dir": checkpoint_dir,
        }
        if model == self.args.model and (
            not checkpoint_dir or checkpoint_dir == self.checkpoint_dir
        ):
            return {
                **out,
                "tier": "resident",
                "predicted_bytes": 0,
                "predicted_bytes_out": 0,
                "predicted_bytes_in": 0,
                "predicted_s": 0.0,
                "measured": True,
            }
        entry = (
            self.model_pool.peek(_pool_key(model, checkpoint_dir))
            if checkpoint_dir
            else self.model_pool.peek_match(model)
        )
        prefetched = entry is not None and isinstance(
            entry.runtime, _PrefetchedWeights
        )
        # costs_view prices many candidates in one call; the outgoing
        # runtime and exec pool are the same for all of them, so it
        # precomputes these once and passes them down
        exec_desc = (
            _exec_desc
            if _exec_desc is not None
            else self.exec_pool.describe()
        )
        compile_est = exec_desc.get("mean_compile_s", 0.0)
        if entry is not None and not prefetched:
            # pool-hit slept runtime: the EXACT planner swap_states will
            # run — byte prediction is deterministic from digests/shapes
            from .sleep import plan_swap

            p = plan_swap(
                self.sleeper,
                entry.runtime.sleeper,
                bucket_bytes=self._swap_bucket_bytes,
                out_digests=(
                    self._runtime.digests if self._content_hash else None
                ),
                in_digests=(
                    entry.runtime.digests if self._content_hash else None
                ),
                quant=self._sleep_quant,
            )
            # zero-drain parked-KV payload rides both directions: the
            # outgoing park's page-out and — when the candidate is a
            # previously-parked runtime — its bundle's page-in. Without
            # these the byte-exactness contract (byte_exact_frac)
            # silently breaks on the first preempting swap.
            park_out = self._park_pageout_bytes()
            pb = getattr(entry.runtime, "parked", None)
            park_in = pb.kv_nbytes if pb is not None else 0
            out_s, m1 = book.seconds_for(
                "swap.d2h", p["wire_out"] + park_out
            )
            in_s, m2 = book.seconds_for(
                "swap.h2d", p["wire_in"] + park_in
            )
            if book.has("swap.total"):
                # effective whole-verb bandwidth from prior pool-hit
                # swaps: predicts the wall directly (fixed per-swap
                # overhead included), which the per-window components
                # can't see
                predicted_s, m_tot = book.seconds_for(
                    "swap.total", p["bytes_moved"] + park_out + park_in
                )
                m1 = m2 = m_tot
            else:
                # one-bucket swaps run the two directions sequentially;
                # the double-buffered overlap needs >= 2 outgoing buckets
                predicted_s = (
                    max(out_s, in_s)
                    if p["buckets_out"] > 1
                    else out_s + in_s
                )
            return {
                **out,
                "tier": "pool",
                "predicted_bytes": p["bytes_moved"] + park_out + park_in,
                "predicted_bytes_out": p["wire_out"] + park_out,
                "predicted_bytes_in": p["wire_in"] + park_in,
                "predicted_kv_pageout_bytes": park_out,
                "predicted_kv_pagein_bytes": park_in,
                "predicted_bytes_deduped": p["bytes_deduped"],
                "predicted_deduped_leaves": p["deduped_leaves"],
                "predicted_bytes_full": p["bytes_full"],
                "quant": p["quant"],
                "predicted_s": round(predicted_s, 6),
                "predicted_d2h_s": round(out_s, 6),
                "predicted_h2d_s": round(in_s, 6),
                "measured": bool(m1 and m2),
                # a slept runtime keeps its compiled programs: no compile
                "compile_estimate_s": 0.0,
            }
        # Cold tiers: the outgoing leg is a level-1 offload of the
        # current runtime; the incoming leg streams a host tree (staged /
        # tier-rebuilt / checkpoint-read) and creates the KV pool — the
        # same figures a cold build's bytes_in reports.
        offload_wire = (
            _offload_wire
            if _offload_wire is not None
            else self._offload_wire_bytes()
        )
        # under zero-drain the offload peeks exclude the KV pool (the
        # park moves the live pages compactly instead): price the park's
        # page-out with the outgoing leg it rides
        park_out = self._park_pageout_bytes()
        d2h_s, m_out = book.seconds_for(
            "sleep.d2h", offload_wire + park_out
        )
        model_cfg = self._model_cfg_cheap(model)
        kv_bytes = self._kv_pool_nbytes(model_cfg)
        read_bytes = 0
        if prefetched:
            tier = "prefetched"
            stream_bytes = int(entry.nbytes)
            params_full = stream_bytes
            if entry.runtime.quant_metas is not None:
                # staged payloads stream compressed; the built engine
                # holds (and bytes_in reports) full-precision arrays
                from ..models import hf as hf_models

                params_full = hf_models.estimate_param_bytes(model_cfg)
        else:
            staged = None
            if self._content_hash:
                if checkpoint_dir:
                    got = self.model_pool.peek_staged(
                        _pool_key(model, checkpoint_dir)
                    )
                    staged = (
                        None if got is None
                        else (got[0], got[1])
                    )
                else:
                    got = self.model_pool.peek_staged_match(model)
                    staged = None if got is None else (got[1], got[2])
            from ..models import hf as hf_models

            params_full = hf_models.estimate_param_bytes(model_cfg)
            if staged is not None:
                nbytes, tier = staged
                stream_bytes = int(nbytes)
                if tier == "disk":
                    read_bytes = stream_bytes
            else:
                tier = "cold"
                stream_bytes = params_full
                read_bytes = params_full
        h2d_s, m_in = book.seconds_for("coldload.h2d", stream_bytes)
        read_s, m_read = (0.0, True)
        if read_bytes:
            read_s, m_read = book.seconds_for("coldload.read", read_bytes)
        # the streaming loaders overlap read with H2D; the offload runs
        # first (sleep, then build)
        predicted_s = d2h_s + max(h2d_s, read_s)
        return {
            **out,
            "tier": tier,
            # what the swap metrics will report as bytes_moved: the
            # offload's wire bytes plus the build's bytes_in (streamed
            # params at full precision once placed, plus the KV pool)
            "predicted_bytes": offload_wire + park_out + params_full
            + kv_bytes,
            "predicted_bytes_out": offload_wire + park_out,
            "predicted_bytes_in": params_full + kv_bytes,
            "predicted_kv_pageout_bytes": park_out,
            "predicted_stream_bytes": stream_bytes,
            "predicted_s": round(predicted_s, 6),
            "predicted_d2h_s": round(d2h_s, 6),
            "predicted_h2d_s": round(h2d_s, 6),
            "predicted_read_s": round(read_s, 6),
            "measured": bool(m_out and m_in and m_read),
            # first-touch compile rides under the transfer when AOT
            # warmup is on (docs/perf.md "Warmup and the executable
            # pool"); reported as its own estimate, not added to
            # predicted_s
            "compile_estimate_s": round(compile_est, 6),
        }

    def price_sleep(self) -> Dict[str, Any]:
        """Predicted cost of a level-1 sleep of the current runtime."""
        if self.sleeper.is_sleeping:
            return {
                "kind": "sleep",
                "model": self.args.model,
                "predicted_bytes": 0,
                "predicted_s": 0.0,
                "measured": True,
            }
        wire = self._offload_wire_bytes()
        # zero-drain: the offload excludes the KV pool (peek_state) and
        # the park pages the live pages out instead — both legs priced
        park = self._park_pageout_bytes()
        s, measured = self.costs.bandwidths.seconds_for(
            "sleep.d2h", wire + park
        )
        return {
            "kind": "sleep",
            "model": self.args.model,
            "predicted_bytes": wire + park,
            "predicted_kv_pageout_bytes": park,
            "predicted_s": round(s, 6),
            "measured": measured,
        }

    def price_wake(self) -> Dict[str, Any]:
        """Predicted cost of waking the current runtime: the slept host
        payload's H2D for level 1, a checkpoint reload estimate for
        level 2."""
        sl = self.sleeper
        if not sl.is_sleeping:
            return {
                "kind": "wake",
                "model": self.args.model,
                "predicted_bytes": 0,
                "predicted_s": 0.0,
                "measured": True,
            }
        if int(sl.level) == 1:
            wire = sl.stats.bytes_offloaded
            # a parked bundle's KV pages back in with the wake (bytes
            # frozen while asleep, so this prediction is exact)
            pb = getattr(self._runtime, "parked", None)
            park_in = pb.kv_nbytes if pb is not None else 0
            s, measured = self.costs.bandwidths.seconds_for(
                "wake.h2d", wire + park_in
            )
            return {
                "kind": "wake",
                "model": self.args.model,
                "predicted_bytes": wire + park_in,
                "predicted_kv_pagein_bytes": park_in,
                "predicted_s": round(s, 6),
                "measured": measured,
            }
        # level 2: the wake re-reads weights (reinit) — a cold load
        model_cfg = self.engine.cfg.model
        from ..models import hf as hf_models

        est = hf_models.estimate_param_bytes(model_cfg)
        h2d_s, m1 = self.costs.bandwidths.seconds_for("coldload.h2d", est)
        read_s, m2 = self.costs.bandwidths.seconds_for(
            "coldload.read", est
        )
        return {
            "kind": "wake",
            "model": self.args.model,
            "predicted_bytes": est,
            "predicted_s": round(max(h2d_s, read_s), 6),
            "measured": bool(m1 and m2),
        }

    def costs_view(
        self, extra: "tuple | list" = ()
    ) -> Dict[str, Any]:
        """GET /v1/costs: every candidate actuation priced in ONE call —
        the resident model, every pooled/prefetched entry, every
        tier-resolvable evicted manifest, plus caller-named extras —
        with the bandwidth book behind the predictions. The scheduler's
        cost input, next to /v1/stats (demand) and the launcher ledger
        (state)."""
        candidates: List[Dict[str, Any]] = []
        seen = set()
        # shared across every candidate: the outgoing leg is the same
        # current runtime, so flatten/plan it once per view, not per row
        exec_desc = self.exec_pool.describe()
        try:
            offload_wire: Optional[int] = self._offload_wire_bytes()
        except Exception:  # noqa: BLE001 — e.g. sleeping: rows degrade per-candidate
            offload_wire = None

        def add(model: str, ckpt: str) -> None:
            key = (model, ckpt)
            if key in seen:
                return
            seen.add(key)
            try:
                candidates.append(
                    self.price_swap(
                        model, ckpt,
                        _offload_wire=offload_wire,
                        _exec_desc=exec_desc,
                    )
                )
            except Exception as e:  # noqa: BLE001 — one bad row never 500s the view
                candidates.append(
                    {
                        "model": model,
                        "checkpoint_dir": ckpt,
                        "error": f"{type(e).__name__}: {e}",
                    }
                )

        add(self.args.model, self.checkpoint_dir)
        for key in self.model_pool.models():
            name, _, ck = key.partition("@")
            add(name, ck)
        for key in self.model_pool.staged_keys():
            name, _, ck = key.partition("@")
            add(name, ck)
        for model, ckpt in extra:
            add(model, ckpt or "")
        return {
            "model": self.args.model,
            "is_sleeping": self.sleeper.is_sleeping,
            "quant": self._sleep_quant,
            "content_hash": self._content_hash,
            "bandwidth_gibps": self.costs.bandwidths.describe(),
            "sleep": self.price_sleep(),
            "wake": self.price_wake(),
            "migrate": self._price_migrate_row(),
            "compile": {
                "mean_compile_s": exec_desc.get("mean_compile_s", 0.0),
                "compiles_total": exec_desc.get("compiles_total", 0),
            },
            "candidates": candidates,
        }

    def _price_migrate_row(self) -> Dict[str, Any]:
        """price_migrate, degraded to an error row instead of 500ing the
        whole /v1/costs view (the sleep/wake row discipline)."""
        try:
            return self.price_migrate()
        except Exception as e:  # noqa: BLE001 — one bad row never 500s the view
            return {"kind": "migrate", "error": f"{type(e).__name__}: {e}"}

    def actuations_view(
        self, n: int = 0, kind: Optional[str] = None
    ) -> Dict[str, Any]:
        """GET /v1/actuations: the decision flight recorder — one
        structured record per actuation this process performed, oldest
        first, plus the oracle-accuracy summary /v1/stats mirrors."""
        return {
            "records": self.costs.recorder.records(n=n, kind=kind),
            "summary": self.costs.recorder.summary(),
        }

    def _record_actuation(
        self,
        kind: str,
        model: str,
        trigger: str,
        tier: str,
        pred: Optional[Dict[str, Any]],
        actual_bytes: int,
        actual_s: float,
        outcome: str = "committed",
        extra: Optional[Dict[str, Any]] = None,
    ):
        """Flight-recorder + metrics choke point: every actuation edge
        lands one record (prediction attached when the oracle priced it
        pre-transfer) and refreshes the per-kind prediction gauges.
        ``extra`` carries structured per-actuation context — zero-drain
        records use it for ``preempted``/``resumed`` counts, so
        /v1/actuations shows what each swap displaced."""
        rec = self.costs.record(
            kind=kind,
            model=model,
            trigger=trigger,
            tier=tier,
            outcome=outcome,
            actual_bytes=actual_bytes,
            actual_s=actual_s,
            extra=extra,
            predicted_bytes=(
                None if pred is None else pred.get("predicted_bytes")
            ),
            predicted_s=(
                None if pred is None else pred.get("predicted_s")
            ),
            measured=bool(pred and pred.get("measured")),
        )
        if rec.predicted_bytes is not None:
            ENGINE_PREDICTED_BYTES.labels(kind=kind).set(
                rec.predicted_bytes
            )
        if rec.seconds_error_ratio is not None and rec.measured:
            ENGINE_COST_ERROR.labels(kind=kind).set(
                rec.seconds_error_ratio
            )
        return rec

    def check_request_model(self, model: Optional[str]) -> None:
        """A completions body's ``model`` names the model being served (by
        name or as ``model@checkpoint_dir``, the pool's key) or is left out;
        anything else is a ValueError (400)."""
        ckpt = getattr(self.args, "checkpoint_dir", "") or ""
        served = _pool_key(self.args.model, ckpt) if ckpt else self.args.model
        if model and model not in (self.args.model, served):
            raise ValueError(
                f"model {model!r} is not served by this engine "
                f"(serving {served!r}); swap to it first"
            )

    def swap(
        self, model: str, checkpoint_dir: str = "", request_id: str = ""
    ) -> Dict[str, Any]:
        """Traced entry for the hot-swap verb: the span adopts whatever
        context the caller established (the HTTP handler's remote
        ``traceparent``), so the engine-side swap tree hangs off the
        launcher's RPC span in one coherent trace. The span carries the
        oracle's pre-transfer prediction (``predicted_bytes`` /
        ``predicted_s``), so every actuation trace records prediction
        vs actual."""
        pred: Optional[Dict[str, Any]] = None
        try:
            pred = self.price_swap(model, checkpoint_dir)
        except Exception:  # noqa: BLE001 — pricing must never block the verb
            pred = None
        with tracing.span(
            "engine.swap",
            model=model,
            previous=self.args.model,
            request_id=request_id,
        ) as sp:
            if pred is not None:
                sp.set(
                    predicted_bytes=pred.get("predicted_bytes"),
                    predicted_s=pred.get("predicted_s"),
                    predicted_tier=pred.get("tier"),
                )
            def record_failure(outcome: str) -> None:
                # the flight recorder must show every failed edge —
                # crash-loop churn is exactly what it exists to audit
                self._record_actuation(
                    "swap", model, trigger="client",
                    tier=pred.get("tier", "") if pred else "",
                    pred=pred, actual_bytes=0, actual_s=0.0,
                    outcome=outcome,
                )

            try:
                out = self._swap_impl(model, checkpoint_dir, request_id)
            except SwapRolledBack:
                record_failure("rolled_back")
                raise
            except ValueError as e:
                # usually a request rejection (unknown model, sleeping
                # engine) — nothing actuated, nothing to record. But a
                # cold BUILD can also raise ValueError subclasses after
                # the outgoing model already slept and rolled back:
                # _swap_impl marks those exceptions (the marker stays
                # true across identical retries, where the degraded
                # message alone would compare equal and hide the churn).
                if getattr(e, "fma_swap_actuated", False):
                    record_failure("failed")
                raise
            except Exception:
                record_failure("failed")
                raise
            sp.set(
                pool_hit=bool(out.get("pool_hit")),
                swapped=bool(out.get("swapped")),
            )
            if out.get("swapped") and not out.get("replayed"):
                for phase, key in (
                    # the *_transfer_s keys carry the pure windows on
                    # every tier (cold swaps' d2h_s is the whole
                    # outgoing sleep verb)
                    ("d2h", "d2h_transfer_s"),
                    ("h2d", "h2d_transfer_s"),
                    ("total", "swap_total_s"),
                ):
                    ENGINE_ACTUATION_SECONDS.labels(
                        kind="swap", phase=phase
                    ).observe(max(0.0, out.get(key, 0.0)))
                zd = out.get("zero_drain") or {}
                if zd.get("restore_shortfall") or zd.get("fallback"):
                    # the prediction modeled a park/resume that didn't
                    # happen as priced: a fallback swap aborted instead
                    # of parking (so the outgoing offload moved the full
                    # pool the peek excluded), or the page-in fell short
                    # (dropped clients / a rolled-back restore). Record
                    # unpriced — the oracle is blameless and a scored
                    # miss would read as digest drift.
                    pred = None
                rec = self._record_actuation(
                    "swap", model, trigger="client",
                    tier=out.get("tier", ""),
                    pred=pred,
                    actual_bytes=out.get("bytes_moved", 0),
                    actual_s=out.get("swap_total_s", 0.0),
                    # what this swap displaced / brought back: the
                    # flight recorder's preemption audit trail
                    extra=(
                        {
                            "preempted": zd.get("parked", 0),
                            "resumed": zd.get("resumed", 0),
                        }
                        if zd
                        else None
                    ),
                )
                out["costs"] = rec.as_dict()
            return out

    def _swap_impl(
        self, model: str, checkpoint_dir: str = "", request_id: str = ""
    ) -> Dict[str, Any]:
        """Hot-swap the model this chip serves (POST /v1/swap): stream the
        current model's state to the host pool while the target's
        host-resident state streams back in, chunked and double-buffered
        (engine/sleep.py swap_states) so the two DMA directions overlap.
        Pool miss = cold build (checkpoint / HF / random init) after a
        chunked offload. No process restart, no chip release: the
        launcher's ChipLedger holder is unchanged.

        **Transactional**: a mid-transfer failure rolls back (the outgoing
        model serves again, the incoming pool entry is re-pooled) and
        raises SwapRolledBack — surfaced as a retryable 503 with /health
        still 200 (DEGRADED); only a failed rollback fails the service.

        ``request_id`` (optional, caller-chosen) makes the verb safely
        retryable across a lost response: a repeat request whose id matches
        the last committed swap replays ``last_swap`` instead of swapping
        again (the launcher's timeout-recovery path reads GET /v1/swap the
        same way)."""
        if self.is_follower or self.engine.lockstep is not None:
            raise ValueError(
                "model hot-swap is not supported for multi-host gangs"
            )
        if model.startswith("hf:"):
            if not model[3:]:
                raise ValueError("swap model hf: needs a directory path")
        elif model not in MODEL_CONFIGS:
            raise ValueError(
                f"unknown model {model!r}; known: {sorted(MODEL_CONFIGS)} "
                "or hf:<model-dir>"
            )
        with self._admin_lock():
            if (
                request_id
                and self.last_swap.get("request_id") == request_id
            ):
                # idempotent replay: this exact swap already committed and
                # the caller lost the answer (timeout / connection drop) —
                # re-executing would swap AWAY from what it asked for
                return dict(self.last_swap, replayed=True)
            previous = self.args.model
            if model == previous and (
                not checkpoint_dir or checkpoint_dir == self.checkpoint_dir
            ):
                return {
                    "model": model,
                    "previous_model": previous,
                    "checkpoint_dir": self.checkpoint_dir,
                    "swapped": False,
                    "pool": self.model_pool.describe(),
                }
            if self.sleeper.is_sleeping:
                raise ValueError(
                    "engine is sleeping; wake_up before swapping models"
                )
            t0 = time.monotonic()
            # Zero-drain (docs/perf.md "Zero-drain actuation"): preempt
            # the outgoing model's live work into a parked bundle instead
            # of aborting it — unless parking is off/ineligible, the
            # bundle would blow the pool budget (it would be evicted—and
            # aborted—immediately), or the page-out itself failed; those
            # fall back to today's abort path below, byte-for-byte.
            parked_bundle = None
            zd_fallback = ""
            if self._zero_drain_parks():
                est = (
                    self._park_pageout_bytes()
                    + self._offload_wire_bytes()
                )
                if est > self.model_pool.budget_bytes:
                    zd_fallback = (
                        f"park rejected: ~{est >> 20} MiB parked state "
                        f"exceeds --model-pool-mib "
                        f"({self.model_pool.budget_bytes >> 20} MiB)"
                    )
                    logger.warning("zero-drain %s; aborting", zd_fallback)
                else:
                    parked_bundle = self._park_current(park_pending=True)
                    if parked_bundle is None:
                        zd_fallback = "park failed (kv page-out)"
            if parked_bundle is None:
                # In-flight AND still-queued work targets the outgoing
                # model (queued prompts were validated against its
                # vocab): fail it now. An otherwise-idle engine keeps
                # its prefix cache — pages move bit-exact, so a
                # swap-back resumes with a warm cache.
                exc = RuntimeError(
                    f"aborted by model swap ({previous} -> {model})"
                )
                # drain one entry at a time: submit() appends lock-free
                # from other threads, and an iterate+clear would drop
                # (and never resolve) an entry appended mid-loop;
                # pop/append on a list are individually atomic
                while self._pending:
                    fut = self._pending.pop(0)[3]
                    if not fut.done():
                        fut.set_exception(exc)
                        # still-queued requests the swap kills count too
                        # — an entry here never reached the engine, so
                        # abort_all below can't see it
                        self._count_abort("swap")
                if self.engine.has_work():
                    self._abort_engine_work(
                        f"model swapped out for {model}", exc, cause="swap"
                    )
            outgoing = self._current_runtime()
            if parked_bundle is not None:
                # rides with the slept runtime into the pool; every
                # failure path below either resumes it (rollback to live
                # serving) or aborts it cleanly (state_loss)
                outgoing.parked = parked_bundle
            # the pool key carries the checkpoint identity: the same model
            # name from a different checkpoint is a different model. A
            # request WITHOUT a checkpoint_dir means "this model, whatever
            # source it came from" — otherwise the natural swap-back
            # {"model": X} would miss a pooled X@/ckpt and silently
            # cold-build random weights under the same name.
            if checkpoint_dir:
                entry = self.model_pool.take(
                    _pool_key(model, checkpoint_dir)
                )
            else:
                entry = self.model_pool.take_match(model)
            pool_hit = entry is not None
            prefetched = pool_hit and isinstance(
                entry.runtime, _PrefetchedWeights
            )
            # AOT warmup accounting for this swap: a slept-runtime pool
            # hit keeps its compiled programs (nothing to warm); the cold
            # and prefetched paths fill this from the build below.
            warm_stats: Optional[Dict[str, Any]] = None
            #: which tier served the incoming weights: pool (slept
            #: runtime) | prefetched (staged host weights) | disk
            #: (chunk-tier manifest reload) | cold (checkpoint/HF read)
            swap_tier = "pool" if pool_hit and not prefetched else "cold"
            if pool_hit and not prefetched:
                rt = entry.runtime
                try:
                    # Delta-aware restore (engine/sleep.py): leaves the
                    # incoming and outgoing models share by content hash
                    # never cross the device boundary — sibling
                    # fine-tunes move only their delta over PCIe.
                    metrics = swap_states(
                        outgoing.sleeper,
                        rt.sleeper,
                        bucket_bytes=self._swap_bucket_bytes,
                        out_digests=(
                            outgoing.digests if self._content_hash else None
                        ),
                        in_digests=(
                            rt.digests if self._content_hash else None
                        ),
                        quant=self._sleep_quant,
                    )
                    # swap_states's windows ARE the pure transfer
                    # windows — the phase=d2h/h2d histogram figures
                    metrics["d2h_transfer_s"] = metrics["d2h_s"]
                    metrics["h2d_transfer_s"] = metrics["h2d_s"]
                except ValueError:
                    # precondition rejections fire before any transfer:
                    # the pooled entry is still intact — put it back under
                    # ITS key (a checkpoint-less request may have matched
                    # a checkpoint-qualified entry). A zero-drain park
                    # already ran, though: put its requests back into
                    # live serving (pool rebuilt, KV paged back in)
                    self._pool_park(entry.model_id, rt, entry.nbytes)
                    self._unpark_current(outgoing)
                    raise
                except SwapRolledBack as e:
                    # mid-transfer failure, rolled back by swap_states:
                    # the outgoing model is awake and serving again and
                    # the incoming entry's host state is untouched —
                    # re-pool it, resume any parked requests (the
                    # rollback's set_state rebuilt the pool), mark
                    # DEGRADED (visible, but /health stays 200), and
                    # surface a retryable 503
                    self._pool_park(entry.model_id, rt, entry.nbytes)
                    self._unpark_current(outgoing)
                    self.degraded = (
                        f"hot-swap {previous}->{model} rolled back: {e}"
                    )
                    ENGINE_RECOVERIES.labels(
                        path="swap", outcome="rolled_back"
                    ).inc()
                    self._new_work.set()
                    logger.warning(
                        "hot-swap %s -> %s rolled back (%s); still "
                        "serving %s", previous, model, e, previous,
                    )
                    raise
                except Exception as e:
                    # rollback failed (SwapRollbackFailed) or an error
                    # outside the transactional window: device state is
                    # partially moved and unrecoverable in-process — fail
                    # the service loudly so /health flips and the
                    # controller heals us, instead of serving from
                    # half-deleted arrays. Parked futures are not in
                    # _futures, so _fail_all can't see them: abort the
                    # bundle explicitly (state_loss).
                    ENGINE_RECOVERIES.labels(
                        path="swap", outcome="rollback_failed"
                    ).inc()
                    self.failure = (
                        f"hot-swap {previous}->{model} failed "
                        f"mid-transfer: {type(e).__name__}: {e}"
                    )
                    if outgoing.parked is not None:
                        b, outgoing.parked = outgoing.parked, None
                        self._abort_parked_bundle(
                            b, previous, self.failure
                        )
                    self._fail_all(RuntimeError(self.failure))
                    raise
            else:
                # Cold build, or a prefetched-weights pool hit: stream the
                # old model out first (HBM bounded by the sleeper's bucket
                # size), then build the new one into the freed space. A
                # prefetched entry skips the checkpoint read — its staged
                # host tree streams straight to device inside the build.
                # The incoming model's AOT warmup is kicked BEFORE the
                # outgoing offload: compilation is host-CPU work over
                # abstract avals, so it rides under both DMA directions
                # (engine/exec_pool.py); pool hits make it a no-op. The
                # model is resolved ONCE here (tokenizer load included)
                # and shared with the build — a resolution failure is
                # deferred to the build, whose rollback path wakes the
                # outgoing model.
                # Disk-tier reload first: an evicted model whose chunks
                # still resolve (host chunks a pooled sibling references,
                # or verified disk-tier blobs) rebuilds from LOCAL tiers
                # — no checkpoint re-read. Any unresolvable chunk made
                # take_staged a miss, so this is all-or-nothing.
                tier_params = tier_digests = None
                tier_ckpt = checkpoint_dir
                tier_src = "disk"
                if not pool_hit and self._content_hash:
                    if checkpoint_dir:
                        got = self.model_pool.take_staged(
                            _pool_key(model, checkpoint_dir)
                        )
                        if got is not None:
                            tier_params, tier_digests, tier_src = got
                    else:
                        got = self.model_pool.take_staged_match(model)
                        if got is not None:
                            tier_params, tier_digests, mkey, tier_src = got
                            tier_ckpt = (
                                mkey.split("@", 1)[1] if "@" in mkey else ""
                            )
                if prefetched:
                    swap_tier = "prefetched"
                elif tier_params is not None:
                    # "host": every chunk was still host-resident via a
                    # sibling's references; "disk": at least one verified
                    # disk-tier reload — the per-tier cost signal must not
                    # attribute DRAM-speed rebuilds to the disk tier
                    swap_tier = tier_src
                resolved = None
                try:
                    resolved = self._resolve_model(model)
                except Exception:  # noqa: BLE001 — the build re-raises it
                    pass
                warm = self._start_warmup(model, resolved=resolved)
                if warm is not None:
                    warm.window_start = time.monotonic()
                try:
                    self.sleeper.sleep(1)
                except Exception as off_exc:
                    # the outgoing offload failed before the build even
                    # started: don't leave the warmup thread compiling for
                    # a swap that is already dead (each retry would kick
                    # another, stacking orphan compile threads)
                    if warm is not None:
                        warm.abort()
                    if outgoing.parked is not None:
                        # a partial offload has no rollback (plain sleep
                        # is not transactional): the parked requests
                        # cannot reliably resume — abort them cleanly
                        b, outgoing.parked = outgoing.parked, None
                        self._abort_parked_bundle(
                            b, previous,
                            f"preempted requests lost: outgoing offload "
                            f"failed mid-swap ({type(off_exc).__name__}: "
                            f"{off_exc})",
                        )
                    # real actuation happened (a partial offload): the
                    # flight recorder must see it even for ValueError-
                    # class failures (see swap()'s handler)
                    off_exc.fma_swap_actuated = True
                    raise
                try:
                    if prefetched:
                        rt = self._build_runtime(
                            model,
                            entry.runtime.checkpoint_dir,
                            staged_params=entry.runtime.params_host,
                            warmup=warm,
                            resolved=resolved,
                            staged_digests=entry.runtime.digests,
                            staged_quant=entry.runtime.quant_metas,
                        )
                    elif tier_params is not None:
                        # weights reconstructed from the chunk tiers:
                        # stream straight host -> device, digests carried
                        # through (they name the same content)
                        rt = self._build_runtime(
                            model, tier_ckpt,
                            staged_params=tier_params,
                            warmup=warm,
                            resolved=resolved,
                            staged_digests=tier_digests,
                        )
                    else:
                        rt = self._build_runtime(
                            model, checkpoint_dir, warmup=warm,
                            resolved=resolved,
                        )
                except Exception as build_exc:
                    # the outgoing model already slept for this build:
                    # whatever happens below (rollback ok or not), the
                    # exception leaving this frame describes a FAILED
                    # ACTUATION, never a request rejection — the flight
                    # recorder keys off this marker (swap()'s handler)
                    build_exc.fma_swap_actuated = True
                    if warm is not None:
                        # swap cancelled: stop compiling between programs
                        # (what already compiled stays pooled for a retry)
                        warm.abort()
                    # a failed build must not leave the chip serving nothing
                    try:
                        self.sleeper.wake_up()
                    except Exception as wake_exc:
                        # the rollback itself failed: the outgoing model
                        # cannot come back — fail the service with BOTH
                        # causes (losing the build error here would send
                        # the operator chasing the wake failure only)
                        ENGINE_RECOVERIES.labels(
                            path="swap_cold", outcome="rollback_failed"
                        ).inc()
                        self.failure = (
                            f"hot-swap {previous}->{model} build failed "
                            f"({type(build_exc).__name__}: {build_exc}) "
                            f"and the rollback wake failed "
                            f"({type(wake_exc).__name__}: {wake_exc})"
                        )
                        if outgoing.parked is not None:
                            b, outgoing.parked = outgoing.parked, None
                            self._abort_parked_bundle(
                                b, previous, self.failure
                            )
                        self._fail_all(RuntimeError(self.failure))
                        raise RuntimeError(self.failure) from build_exc
                    if prefetched:
                        # the staged host weights are untouched by a
                        # failed build: re-pool them for the next attempt
                        self._pool_park(
                            entry.model_id, entry.runtime, entry.nbytes
                        )
                    elif tier_params is not None:
                        # tier-staged weights are untouched too: re-pool
                        # them as prefetched host weights (take_staged
                        # consumed the manifest — without this, a
                        # transient build failure costs the retry a full
                        # checkpoint re-read despite every chunk sitting
                        # verified on local tiers)
                        import jax

                        nb = sum(
                            x.nbytes for x in jax.tree.leaves(tier_params)
                        )
                        self._pool_park(
                            _pool_key(model, tier_ckpt),
                            _PrefetchedWeights(
                                model_id=model,
                                checkpoint_dir=tier_ckpt,
                                params_host=tier_params,
                                nbytes=nb,
                                digests=tier_digests,
                            ),
                            nb,
                        )
                    ENGINE_RECOVERIES.labels(
                        path="swap_cold", outcome="rolled_back"
                    ).inc()
                    # the rollback wake rebuilt the outgoing engine's
                    # state (fresh pool under zero-drain): put its
                    # preempted requests back into live serving
                    self._unpark_current(outgoing)
                    self.degraded = (
                        f"hot-swap {previous}->{model} build failed; "
                        f"rolled back to {previous}: "
                        f"{type(build_exc).__name__}: {build_exc}"
                    )
                    raise
                # A pool-miss swap still transfers the whole incoming
                # model to HBM inside the build — report the build's H2D
                # window/bytes instead of zeros, so swap_overlap_frac and
                # dashboards aren't lying on misses (the overlap here is
                # the cold loader's read/H2D overlap, not a two-direction
                # DMA overlap).
                b = self._last_build_stats
                warm_stats = b.get("warmup")
                out_stats = outgoing.sleeper.stats
                cold_moved = (
                    out_stats.bytes_offloaded + b.get("bytes_in", 0)
                )
                cold_full = (
                    out_stats.bytes_offloaded_full + b.get("bytes_in", 0)
                )
                metrics = {
                    "swap_total_s": 0.0,  # finalized below
                    "d2h_s": out_stats.last_sleep_seconds,
                    "h2d_s": b.get("h2d_s", 0.0),
                    # the pure transfer windows for the phase histogram:
                    # d2h_s above is the whole outgoing sleep verb
                    # (quiesce included), which must not pollute the
                    # "transfer window" percentiles
                    "d2h_transfer_s": out_stats.last_sleep_transfer_s,
                    "h2d_transfer_s": b.get("h2d_s", 0.0),
                    "overlap_s": b.get("overlap_s", 0.0),
                    "overlap_frac": b.get("overlap_frac", 0.0),
                    "bytes_out": out_stats.bytes_offloaded,
                    "bytes_in": b.get("bytes_in", 0),
                    # full transfer in both directions: a build streams
                    # the whole incoming model regardless of content.
                    # Under --sleep-quant the OUTGOING offload still moved
                    # only payload bytes (bytes_full records the
                    # uncompressed total, same contract as swap_states).
                    "bytes_moved": cold_moved,
                    "bytes_deduped": 0,
                    "deduped_leaves": 0,
                    "quant": out_stats.last_quant,
                    "quant_leaves": 0,
                    "bytes_full": cold_full,
                    "bytes_saved_quant": max(0, cold_full - cold_moved),
                    "buckets_out": 0,
                    "buckets_in": b.get("buckets_in", 0),
                    "bucket_bytes": self._swap_bucket_bytes,
                    "peak_bytes_in_flight": 0,
                }
            evicted = self._pool_park(
                _pool_key(previous, outgoing.checkpoint_dir),
                outgoing,
                # the parked-request bundle is host state the pool must
                # byte-count like the slept weights it rides with
                nbytes=outgoing.sleeper.stats.bytes_offloaded
                + (parked_bundle.nbytes if parked_bundle else 0),
            )
            self._free_pooled(evicted, "evicted over pool budget")
            self._install_runtime(rt)
            # swap-back to a previously-parked runtime: page its KV back
            # in and resume the preempted streams mid-decode (a restore
            # failure aborts them cleanly inside _resume_parked and the
            # swap still commits — the engine serves either way)
            zd_resumed, zd_pagein, _zd_resume_s, zd_dropped, zd_short = (
                self._resume_parked(rt)
            )
            if self._zero_drain:
                metrics["kv_pageout_bytes"] = (
                    parked_bundle.kv_nbytes if parked_bundle else 0
                )
                metrics["kv_pagein_bytes"] = zd_pagein
                # parked KV is actuation payload: it counts into the
                # byte totals the oracle predicts and the record scores
                extra_kv = metrics["kv_pageout_bytes"] + zd_pagein
                if extra_kv:
                    metrics["bytes_out"] += metrics["kv_pageout_bytes"]
                    metrics["bytes_in"] += zd_pagein
                    metrics["bytes_moved"] += extra_kv
                    metrics["bytes_full"] += extra_kv
            if model != previous:
                # same-name variant swaps (sibling checkpoints) keep the
                # label series AND the arrival EWMA: the name — which is
                # what every per-model series is keyed by — didn't change,
                # so nothing went stale and demand history is still true
                self._retire_model_series(previous)
            total = time.monotonic() - t0
            metrics["swap_total_s"] = total
            ENGINE_SWAP_SECONDS.labels(model=model).observe(total)
            ENGINE_SWAPS.labels(
                model=model, source="pool" if pool_hit else "cold"
            ).inc()
            self._bump_actuation("swap")
            if pool_hit:
                ENGINE_POOL_HITS.inc()
            ENGINE_SWAP_OVERLAP_FRAC.labels(model=model).set(
                metrics.get("overlap_frac", 0.0)
            )
            ENGINE_SWAP_INFLIGHT_BYTES.labels(model=model).set(
                metrics.get("peak_bytes_in_flight", 0)
            )
            ENGINE_SWAP_DELTA_BYTES.labels(model=model, kind="moved").set(
                metrics.get("bytes_moved", 0)
            )
            ENGINE_SWAP_DELTA_BYTES.labels(model=model, kind="deduped").set(
                metrics.get("bytes_deduped", 0)
            )
            # per-mode wire-byte accounting (docs/metrics.md): what the
            # compressed path actually moved, by direction
            swap_quant = metrics.get("quant", "off") or "off"
            ENGINE_ACTUATION_BYTES.labels(mode=swap_quant, dir="d2h").inc(
                metrics.get("bytes_out", 0)
            )
            ENGINE_ACTUATION_BYTES.labels(mode=swap_quant, dir="h2d").inc(
                metrics.get("bytes_in", 0)
            )
            # a committed swap is proof the failure domain healed: clear
            # any DEGRADED marker from an earlier rolled-back attempt
            self.degraded = None
            self.last_swap = {
                "model": model,
                "previous_model": previous,
                "request_id": request_id,
                # the installed runtime's checkpoint identity (pooled
                # runtimes remember theirs): the launcher rewrites its
                # stored options from THIS, not from the request, so a
                # restart rebuilds what the chip actually serves
                "checkpoint_dir": rt.checkpoint_dir,
                "swapped": True,
                "pool_hit": pool_hit,
                # pool_hit via background prefetch: source="pool" but the
                # entry was staged weights, not a slept runtime
                "prefetched": prefetched,
                # which tier served the incoming weights (docs/perf.md
                # "Tiered weight cache and delta swap")
                "tier": swap_tier,
                # zero-drain accounting (absent with the flag off, so
                # off-mode responses are unchanged byte-for-byte):
                # what this swap displaced and what it brought back
                **(
                    {
                        "zero_drain": {
                            "parked": (
                                parked_bundle.preempted
                                if parked_bundle
                                else 0
                            ),
                            "resumed": zd_resumed,
                            "kv_pageout_bytes": metrics.get(
                                "kv_pageout_bytes", 0
                            ),
                            "kv_pagein_bytes": metrics.get(
                                "kv_pagein_bytes", 0
                            ),
                            # parked requests whose clients vanished:
                            # their pages never paged back in, so the
                            # record is scored unpriced (swap())
                            **(
                                {"dropped": zd_dropped}
                                if zd_dropped
                                else {}
                            ),
                            # page-in moved fewer bytes than the bundle
                            # predicted (dropped clients or a failed
                            # restore): unpriced record (swap())
                            **(
                                {"restore_shortfall": True}
                                if zd_short
                                else {}
                            ),
                            **(
                                {"fallback": zd_fallback}
                                if zd_fallback
                                else {}
                            ),
                        }
                    }
                    if self._zero_drain
                    else {}
                ),
                **{
                    k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in metrics.items()
                },
                "builds_total": self.builds_total,
                "pool": self.model_pool.describe(),
                # hidden-compile accounting (None on a slept-runtime pool
                # hit — its executables rode the pooled engine)
                "warmup": warm_stats,
                "exec_pool": self.exec_pool.describe(),
            }
            out = dict(self.last_swap)
        self._publish_usage()
        self._new_work.set()
        logger.info(
            "hot-swapped model %s -> %s (pool_hit=%s, %.3fs, overlap %.0f%%)",
            previous, model, pool_hit, total,
            100 * metrics.get("overlap_frac", 0.0),
        )
        return out

    # -- background checkpoint prefetch --------------------------------------

    def prefetch(self, model: str, checkpoint_dir: str = "") -> Dict[str, Any]:
        """Start a background checkpoint prefetch (POST /v1/prefetch):
        stage `model`'s weights host-resident into the model pool — never
        touching HBM, I/O-throttled (--prefetch-mib-s), abortable — so the
        first-ever swap to it takes the warm (pool) path while the current
        model keeps serving. The dual-pods controller uses this to hint
        the predicted next model."""
        if self.is_follower or self.engine.lockstep is not None:
            raise ValueError("prefetch is not supported for multi-host gangs")
        if not model.startswith("hf:"):
            raise ValueError(
                "prefetch requires an hf:<model-dir> model (named configs "
                "are random-init, and Orbax checkpoints restore straight "
                "into device placement on swap)"
            )
        if checkpoint_dir:
            # Staging can only read the HF directory. Pooling HF base
            # weights under model@checkpoint_dir would make the later swap
            # silently serve them where a non-prefetched swap restores the
            # Orbax checkpoint — wrong weights, not a slow path.
            raise ValueError(
                "prefetch cannot stage an Orbax checkpoint_dir (it reads "
                "the hf: directory only); swap to the checkpoint directly"
            )
        hf_dir = model[3:]
        if not hf_dir:
            raise ValueError("prefetch model hf: needs a directory path")
        if model == self.args.model and (
            not checkpoint_dir or checkpoint_dir == self.checkpoint_dir
        ):
            raise ValueError(f"{model} is already the serving model")
        key = _pool_key(model, checkpoint_dir)
        if (
            key in self.model_pool
            if checkpoint_dir
            else self.model_pool.contains_match(model)
        ):
            return {
                "state": "already_pooled",
                "model": model,
                "checkpoint_dir": checkpoint_dir,
                "started": False,
            }
        if self._content_hash:
            # tier fast path: an evicted model whose chunks still resolve
            # (host or disk tier) stages with ZERO source reads
            got = self.model_pool.take_staged(_pool_key(model, checkpoint_dir))
            if got is not None:
                import jax

                tree, tier_digests, tier_src = got
                nbytes = sum(x.nbytes for x in jax.tree.leaves(tree))
                pw = _PrefetchedWeights(
                    model_id=model,
                    checkpoint_dir=checkpoint_dir,
                    params_host=tree,
                    nbytes=nbytes,
                    digests=tier_digests,
                )
                evicted = self._pool_park(
                    _pool_key(model, checkpoint_dir), pw, nbytes
                )
                bounced = any(v.runtime is pw for v in evicted)
                self._free_pooled(evicted, "evicted by prefetch")
                if not bounced:
                    ENGINE_PREFETCHES.labels(outcome="completed").inc()
                    ENGINE_PREFETCH_BYTES.set(nbytes)
                    self.last_prefetch = {
                        "state": "completed",
                        "model": model,
                        "checkpoint_dir": checkpoint_dir,
                        "bytes": nbytes,
                        "source": "tier",
                        "tier": tier_src,
                        "pool": self.model_pool.describe(),
                    }
                    return dict(self.last_prefetch, started=False)
        from ..models import hf as hf_models

        model_cfg = hf_models.config_from_hf(
            hf_dir, quantization=self.args.quantization or ""
        )
        # quant-aware admission: an int8/fp8-staged model occupies its
        # payload bytes, not 2x that — the estimate must agree with what
        # the worker below actually pools
        est = hf_models.estimate_param_bytes(
            model_cfg,
            transfer_quant=self._sleep_quant,
            hot_head=self._sleep_quant_hot_head,
        )
        if est > self.model_pool.budget_bytes:
            ENGINE_PREFETCHES.labels(outcome="rejected").inc()
            raise ValueError(
                f"prefetch of {model} (~{est >> 20} MiB staged) exceeds "
                f"the model pool budget "
                f"({self.model_pool.budget_bytes >> 20} MiB); raise "
                "--model-pool-mib"
            )
        with self._prefetch_mu:
            if (
                self._prefetch_thread is not None
                and self._prefetch_thread.is_alive()
            ):
                raise ValueError(
                    "a prefetch is already in progress "
                    "(DELETE /v1/prefetch aborts it)"
                )
            self._prefetch_abort = threading.Event()
            self.last_prefetch = {
                "state": "running",
                "model": model,
                "checkpoint_dir": checkpoint_dir,
                "bytes": 0,
            }
            self._prefetch_thread = threading.Thread(
                target=self._prefetch_worker,
                args=(
                    model, hf_dir, checkpoint_dir, model_cfg,
                    self._prefetch_abort,
                    # the caller's span context, captured HERE: ContextVars
                    # do not cross into the staging thread on their own
                    tracing.current_context(),
                ),
                daemon=True,
                name="prefetch",
            )
            self._prefetch_thread.start()
        return dict(self.last_prefetch, started=True)

    def _prefetch_worker(
        self, model, hf_dir, checkpoint_dir, model_cfg, abort, trace_ctx=None
    ) -> None:
        """Prefetch thread body: host-only staging (load_params with
        place=False — pure file I/O + numpy, no device/HBM touch), then
        registration in the pool under the swap's key."""
        from ..models import hf as hf_models

        worker_sp = tracing.begin(
            "engine.prefetch", parent=trace_ctx, model=model
        )
        t0 = time.monotonic()
        # Executables stage alongside weights: the warmup compiles on its
        # own thread while this one reads shards, so a first-ever swap to
        # a prefetched model finds warm weights AND warm executables in
        # the pools — fully warm, zero compile on the swap edge.
        warm = self._start_warmup(model)
        if warm is not None:
            warm.window_start = t0
        lstats = hf_models.LoadStats()
        try:
            faults.fire("prefetch.stage")
            staged = hf_models.load_params(
                hf_dir,
                model_cfg,
                place=False,
                workers=getattr(self.args, "load_workers", 0) or None,
                abort_event=abort,
                throttle_bytes_per_s=float(
                    max(0, getattr(self.args, "prefetch_mib_s", 0)) << 20
                ),
                stats=lstats,
                want_digests=self._content_hash,
            )
        except hf_models.LoadAborted:
            if warm is not None:
                warm.abort()
            ENGINE_PREFETCHES.labels(outcome="aborted").inc()
            self.last_prefetch = {
                "state": "aborted",
                "model": model,
                "checkpoint_dir": checkpoint_dir,
                "bytes": lstats.bytes_read,
            }
            worker_sp.set(state="aborted")
            worker_sp.end()
            return
        except Exception as e:  # noqa: BLE001 — surfaced via GET /v1/prefetch
            if warm is not None:
                warm.abort()
            logger.warning("prefetch of %s failed", model, exc_info=True)
            ENGINE_PREFETCHES.labels(outcome="failed").inc()
            self.last_prefetch = {
                "state": "failed",
                "model": model,
                "checkpoint_dir": checkpoint_dir,
                "error": f"{type(e).__name__}: {e}",
            }
            worker_sp.set(state="failed", error=f"{type(e).__name__}: {e}")
            worker_sp.end()
            return
        # end of the staging window the compiles could hide under — stamped
        # BEFORE joining the warmup thread below, or compile seconds spent
        # after the staging finished would count as "hidden" and the
        # reported hidden_frac would read ~1.0 regardless of actual overlap
        t_staged = time.monotonic()
        import jax

        quant_metas = None
        if self._sleep_quant != "off":
            # compressed staging (docs/perf.md "Compressed actuation"):
            # quantize host-side while no one is waiting — the pool holds
            # payload bytes (~2x models per GiB) and the consuming swap
            # streams payloads + dequantizes on device. The fp digests
            # describe content this entry no longer carries; the pool
            # interns payloads under transfer digests instead.
            from ..models import quant as transfer_quant

            plan = transfer_quant.transfer_quant_plan(
                staged, hot_head=self._sleep_quant_hot_head, prefix=""
            )
            if any(plan):
                leaves, treedef = jax.tree.flatten(staged)
                quant_metas = [None] * len(leaves)
                for i, flag in enumerate(plan):
                    if flag:
                        leaves[i], quant_metas[i] = (
                            transfer_quant.quantize_leaf_np(
                                leaves[i], self._sleep_quant
                            )
                        )
                staged = jax.tree.unflatten(treedef, leaves)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(staged)) + (
            sum(m.scale_nbytes for m in quant_metas if m is not None)
            if quant_metas is not None
            else 0
        )
        pw = _PrefetchedWeights(
            model_id=model,
            checkpoint_dir=checkpoint_dir,
            params_host=staged,
            nbytes=nbytes,
            digests=(
                self._qualify_digests(
                    dict(lstats.digests) or None, model_cfg
                )
                if self._content_hash and quant_metas is None
                else None
            ),
            quant_metas=quant_metas,
            quant_mode=self._sleep_quant if quant_metas else "off",
        )
        evicted = self._pool_park(
            _pool_key(model, checkpoint_dir), pw, nbytes
        )
        bounced = any(v.runtime is pw for v in evicted)
        self._free_pooled(evicted, "evicted by prefetch")
        if bounced:
            # raced a concurrent budget change / the estimate was low: the
            # staging cannot be kept
            if warm is not None:
                # same as the aborted/failed branches: stop compiling for
                # a model that failed to stage (what compiled stays pooled)
                warm.abort()
            ENGINE_PREFETCHES.labels(outcome="rejected").inc()
            self.last_prefetch = {
                "state": "rejected",
                "model": model,
                "checkpoint_dir": checkpoint_dir,
                "bytes": nbytes,
                "error": "staged bytes exceed the model pool budget",
            }
            worker_sp.set(state="rejected")
            worker_sp.end()
            return
        warm_stats = None
        if warm is not None:
            # the staging window is the transfer the compiles hid under
            warm.wait(600)
            warm_stats = warm.overlap_stats(window_t1=t_staged)
        ENGINE_PREFETCHES.labels(outcome="completed").inc()
        ENGINE_PREFETCH_BYTES.set(nbytes)
        self.last_prefetch = {
            "state": "completed",
            "model": model,
            "checkpoint_dir": checkpoint_dir,
            "bytes": nbytes,
            # staged representation: "off" = full precision, else the
            # transfer mode the pooled payload carries
            "quant": pw.quant_mode,
            "read_s": round(lstats.read_s, 6),
            "total_s": round(time.monotonic() - t0, 6),
            "shards": lstats.shards,
            "workers": lstats.workers,
            "pool": self.model_pool.describe(),
            # executables staged alongside the weights (exec_pool.py):
            # what the first-ever swap to this model will pool-hit
            "warmup": warm_stats,
            "exec_pool": self.exec_pool.describe(),
        }
        worker_sp.set(state="completed", bytes=nbytes)
        worker_sp.end()
        logger.info(
            "prefetched %s host-resident (%.1f MiB in %.3fs)",
            model, nbytes / 2**20, time.monotonic() - t0,
        )

    def prefetch_status(self) -> Dict[str, Any]:
        return dict(self.last_prefetch)

    def abort_prefetch(self) -> Dict[str, Any]:
        """Cancel the in-flight prefetch (DELETE /v1/prefetch): readers
        observe the abort event between tensors and unwind without ever
        registering in the pool."""
        with self._prefetch_mu:
            t = self._prefetch_thread
            if t is None or not t.is_alive():
                return {
                    "aborted": False,
                    "state": self.last_prefetch.get("state", "idle"),
                }
            self._prefetch_abort.set()
        t.join(timeout=60)
        return {"aborted": True, **self.last_prefetch}

    # -- on-demand deep profiling (POST/DELETE /v1/profile) -------------------

    def start_profile(
        self, log_dir: str = "", python_tracer: bool = False
    ) -> Dict[str, Any]:
        """Start a jax.profiler capture (XLA device + host activity,
        viewable in Perfetto / TensorBoard) — the "why is THIS phase slow"
        microscope the span timeline points at. Gated to one concurrent
        capture: the profiler is process-global state.

        The Python tracer is off unless ``python_tracer`` asks for it: it
        slows the host it measures and the events it adds are per Python
        call, while the scheduler phases (``tracing.phase``), jaxlib's
        own host events and the device planes are all recorded without
        it. While the capture runs every phase also leaves a span and a
        ``TraceAnnotation``; ``fma.clock`` marks this process's span
        clock in the capture."""
        import jax

        with self._profile_mu:
            if self._profile_dir is not None:
                raise ProfileConflict(
                    f"a profile capture is already running "
                    f"(log_dir={self._profile_dir}); DELETE /v1/profile "
                    "stops it"
                )
            log_dir = log_dir or os.path.join(
                "/tmp", f"fma-profile-{os.getpid()}-{int(time.time())}"
            )
            os.makedirs(log_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python_tracer else 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
            self._profile_dir = log_dir
            self._profile_span = tracing.begin(
                "profile.capture", activate=False, log_dir=log_dir,
                python_tracer=python_tracer,
            )
            tracing.capture_started()
        logger.info(
            "jax profiler capture started -> %s (python tracer %s)",
            log_dir, "on" if python_tracer else "off",
        )
        return {
            "profiling": True, "log_dir": log_dir,
            "python_tracer": python_tracer,
        }

    def stop_profile(self) -> Dict[str, Any]:
        import jax

        with self._profile_mu:
            if self._profile_dir is None:
                raise ProfileConflict("no profile capture is running")
            # stop FIRST, clear state only on success: a raising
            # stop_trace (deleted log_dir, export error) must leave the
            # capture marked running so a retried DELETE can reach the
            # still-active process-global profiler — clearing first would
            # wedge the API (409 forever, start_trace 500s) until restart.
            # The phases stop annotating before the stop is attempted,
            # whatever becomes of it: the capture span is start -> here.
            tracing.capture_stopped()
            self._profile_span.end()
            t0 = time.monotonic()
            jax.profiler.stop_trace()
            stop_s = time.monotonic() - t0
            log_dir, self._profile_dir = self._profile_dir, None
        logger.info(
            "jax profiler capture stopped (%s), trace written in %.3f s",
            log_dir, stop_s,
        )
        return {"profiling": False, "log_dir": log_dir}

    def profile_status(self) -> Dict[str, Any]:
        with self._profile_mu:
            return {
                "profiling": self._profile_dir is not None,
                "log_dir": self._profile_dir or "",
            }

    def _make_publisher(self):
        chip_ids = [c for c in os.environ.get("FMA_CHIP_IDS", "").split(",") if c]
        if not chip_ids:
            return None
        from ..native.hbm_publisher import HbmUsagePublisher

        return HbmUsagePublisher(chip_ids)

    def _publish_usage(self) -> None:
        """Report live HBM bytes to the cooperative usage protocol so the
        requester SPI / controller budget check see this process the way the
        reference sees a CUDA process through nvidia-smi."""
        if self._publisher is None:
            return
        if self.sleeper.is_sleeping:
            self._publisher.set_uniform(0)
        else:
            state = {"p": self.engine.params, "kv": self.engine.pool.as_tuple()}
            import jax

            nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
            self._publisher.set_uniform(nbytes)

    # -- engine thread -------------------------------------------------------

    def _drain_aborts(self) -> None:
        """Apply client-disconnect aborts on the engine thread (the only
        thread allowed to touch engine scheduler state)."""
        while self._abort_q:
            fut = self._abort_q.pop(0)
            # still pending? drop it before admission
            for i, entry in enumerate(self._pending):
                if entry[3] is fut:
                    self._pending.pop(i)
                    if entry[15] is not None:
                        # tail-keep: aborted lifecycles always retain
                        entry[15].finish(
                            entry[14], time.monotonic(), keep=True,
                            outcome="aborted",
                        )
                    break
            seq_id = self._fut_seq.pop(id(fut), None)
            if seq_id is not None:
                req = self._find_live_request(seq_id)
                if self.engine.abort(seq_id, reason="client disconnected"):
                    self._count_abort("client")
                    if req is not None:
                        self._finish_request_trace(
                            req, time.monotonic(), aborted=True,
                            outcome="aborted",
                        )
                self._futures.pop(seq_id, None)
            else:
                rec = self._proxied.pop(id(fut), None)
                if rec is not None:
                    # migrated-away stream whose client dropped: the
                    # claim watcher exits silently on fut.done(), so the
                    # ONE source-side client abort is counted here (the
                    # outcome was already committed as "migrated" at
                    # release), and the destination is told to abort its
                    # claim — it stops decoding and counts its own
                    # single client abort
                    self._count_abort("client")
                    self._abort_claims_async(
                        rec.get("dest", ""), [rec.get("claim", "")]
                    )
            if not fut.done():
                fut.cancel()

    def _run(self) -> None:
        """The scheduler loop. Each part of an iteration is a
        ``tracing.phase`` (docs/tracing.md "Scheduler phases"); the
        engine's own parts are named inside ``engine.step``."""
        while not self._stop:
            stepped = False
            try:
                with self._lock:
                    self._intake()
                    if (
                        not self.sleeper.is_sleeping
                        and self.engine.has_work()
                    ):
                        finished = self.engine.step()
                        with tracing.phase(
                            "sched.observe", self.engine.chunk_in_flight
                        ):
                            for req in finished:
                                req.done_time = time.monotonic()
                                # observe BEFORE resolving: the usage
                                # block reads req.trace_id, stamped by
                                # the trace finish inside observe
                                self._observe_finished(req)
                                fut = self._futures.pop(req.seq_id, None)
                                if fut is not None:
                                    self._fut_seq.pop(id(fut), None)
                                    if not fut.done():
                                        fut.set_result(req)
                            self._observe_kv_usage()
                            self._observe_step()
                        stepped = True
            except Exception as e:  # device/runtime failure: fail loudly
                logger.exception("engine loop failed")
                self.failure = f"{type(e).__name__}: {e}"
                self._fail_all(RuntimeError(self.failure))
                return
            if stepped and not self._admin_waiting:
                continue
            with tracing.phase("sched.wait"):
                if stepped:
                    # hand the just-released lock to the waiting
                    # sleep/wake/swap instead of re-grabbing it hot — an
                    # unfair lock can starve the admin call for a whole
                    # generation
                    time.sleep(0.002)
                else:
                    self._new_work.wait(timeout=0.05)
                    self._new_work.clear()

    def _intake(self) -> None:
        """Aborts, then every pending request into the engine's waiting
        queue (engine thread, service lock held)."""
        with tracing.phase(
            "sched.intake", self.engine.chunk_in_flight
        ) as ph:
            self._drain_aborts()
            if self.sleeper.is_sleeping:
                return
            ph.set(requests=len(self._pending))
            while self._pending:
                (
                    prompt, max_tokens, temperature, fut,
                    on_tokens, top_p, stop_seqs, presence, freq,
                    want_alts, want_plp, seed, ignore_eos,
                    logit_bias, submit_t, trace, stop_watch,
                ) = self._pending.pop(0)
                try:
                    seq_id = self.engine.add_request(
                        prompt, max_tokens, temperature,
                        top_p=top_p, stop_seqs=stop_seqs,
                        presence_penalty=presence,
                        frequency_penalty=freq,
                        on_tokens=on_tokens,
                        stop_watch=stop_watch,
                        want_top_logprobs=want_alts,
                        want_prompt_logprobs=want_plp,
                        seed=seed,
                        ignore_eos=ignore_eos,
                        logit_bias=logit_bias,
                        submit_time=submit_t,
                        trace=trace,
                    )
                    self._futures[seq_id] = fut
                    self._fut_seq[id(fut)] = seq_id
                except Exception as e:
                    if trace is not None:
                        # rejected at admission: tail-keep
                        # (an aborted lifecycle, however
                        # short, is exactly what to debug)
                        trace.finish(
                            submit_t, time.monotonic(),
                            keep=True, outcome="rejected",
                            error=f"{type(e).__name__}: {e}",
                        )
                    fut.set_exception(e)

    def _observe_finished(self, req) -> None:
        m = self.args.model
        now = time.monotonic()
        if req.done_time is not None:
            # step() stamps this before resolving the future; direct
            # engine users (tests) may not have a serving loop
            now = req.done_time
        ttft = None
        if req.first_token_time is not None:
            ttft = req.first_token_time - req.submit_time
            ENGINE_TTFT.labels(model=m).observe(ttft)
        if req.first_sched_time is not None:
            # the queue leg of TTFT: submit -> first slot (prefill and
            # decode come after) — what an actuation-induced stall shows
            # up in, separately from prefill speed
            ENGINE_QUEUE_WAIT.labels(model=m).observe(
                max(0.0, req.first_sched_time - req.submit_time)
            )
        ENGINE_E2E_LATENCY.labels(model=m).observe(now - req.submit_time)
        ENGINE_PROMPT_TOKENS.labels(model=m).inc(len(req.prompt))
        gen = len(req.out_tokens)
        ENGINE_GENERATED_TOKENS.labels(model=m).inc(gen)

        # SLO judgment (docs/perf.md "Fleet benchmarking and goodput"):
        # each enabled target is judged independently; goodput counts a
        # request's tokens only when NO enabled target was violated
        # (vacuously all of them, when none is configured).
        met_all = True
        evaluated = False
        violated_slos: List[str] = []
        if self._slo_ttft_s > 0:
            ok = ttft is not None and ttft <= self._slo_ttft_s
            ENGINE_SLO_REQUESTS.labels(
                model=m, slo="ttft", outcome="met" if ok else "violated"
            ).inc()
            if not ok:
                violated_slos.append("ttft")
            met_all = met_all and ok
            evaluated = True
        if self._slo_tpot_s > 0:
            if req.first_token_time is not None and gen > 1:
                tpot = (now - req.first_token_time) / (gen - 1)
                ok = tpot <= self._slo_tpot_s
            else:
                # a single-token (or token-less error) request has no
                # inter-token interval to judge
                ok = req.first_token_time is not None
            ENGINE_SLO_REQUESTS.labels(
                model=m, slo="tpot", outcome="met" if ok else "violated"
            ).inc()
            if not ok:
                violated_slos.append("tpot")
            met_all = met_all and ok
            evaluated = True
        if met_all:
            ENGINE_GOODPUT_TOKENS.labels(model=m).inc(gen)
        violated = evaluated and not met_all
        trace_id = self._finish_request_trace(
            req, now, violated=violated,
            aborted=bool(getattr(req, "error", None)),
        )
        with self._slo_mu:
            self._finished_requests += 1
            self._generated_tokens += gen
            if met_all:
                self._goodput_tokens += gen
            if evaluated:
                if met_all:
                    self._slo_met += 1
                else:
                    self._slo_violated += 1
            if violated and trace_id:
                self._slo_exemplars.append(
                    {
                        "trace_id": trace_id,
                        "model": m,
                        "violated": violated_slos,
                        "ttft_s": None if ttft is None else round(ttft, 6),
                        "legs": {
                            k: round(v, 6)
                            for k, v in self._request_legs(
                                req, now
                            ).items()
                        },
                    }
                )

    def _request_legs(self, req, now: float) -> Dict[str, float]:
        """Decompose submit→done into the leg durations the SLO
        exemplars bucket by. Preemption wall time is INSIDE the raw
        queue/prefill/decode windows (the stamps don't pause while
        parked), so it is carved out — the
        pre-first-token share from queue first, then prefill; the rest
        from decode — leaving {queue, prefill, decode, preempt} a
        partition of the request's server-side wall time."""
        pre = max(0.0, getattr(req, "preempt_pre_token_s", 0.0))
        total_pre = max(0.0, getattr(req, "preempt_s", 0.0))
        if req.first_sched_time is None:
            queue = max(0.0, now - req.submit_time)
            prefill = decode = 0.0
        else:
            queue = max(0.0, req.first_sched_time - req.submit_time)
            if req.first_token_time is not None:
                prefill = max(
                    0.0, req.first_token_time - req.first_sched_time
                )
                decode = max(0.0, now - req.first_token_time)
            else:
                prefill = max(0.0, now - req.first_sched_time)
                decode = 0.0
        take = min(queue, pre)
        queue -= take
        prefill = max(0.0, prefill - (pre - take))
        decode = max(0.0, decode - (total_pre - pre))
        return {
            "queue": queue,
            "prefill": prefill,
            "decode": decode,
            "preempt": total_pre,
            "migrate": 0.0,
        }

    def _finish_request_trace(
        self,
        req,
        now: float,
        violated: bool = False,
        aborted: bool = False,
        migrated: bool = False,
        outcome: str = "finished",
    ) -> str:
        """Close out a request's lifecycle trace: decide retention
        (head-sample draw OR tail-keep on violation/abort/migration),
        record the one whole-window decode span, flush to the request
        ring, and stamp req.trace_id for the usage block. At
        --trace-requests 0 a violated/aborted request still gets a
        retained trace, synthesized here from the Request's timestamps —
        the hot path recorded nothing for it. Returns the trace_id when
        spans were retained, else ''."""
        if getattr(req, "_trace_done", False):
            # a request can reach two finish paths (engine abort, then
            # the step loop's finished list): first one wins
            return req.trace_id
        req._trace_done = True
        tr = getattr(req, "trace", None)
        if tr is None:
            if not (violated or aborted) or not tracing.enabled():
                return ""
            tr = tracing.RequestTrace(sampled=True)
            if req.first_sched_time is not None:
                tr.add(
                    "request.queue", req.submit_time, req.first_sched_time
                )
                first_tok = req.first_token_time
                tr.add(
                    "request.prefill",
                    req.first_sched_time,
                    first_tok if first_tok is not None else now,
                    prompt_tokens=len(req.prompt),
                    cached_tokens=req.cached_tokens,
                    synthesized=True,
                )
        if (
            req.first_token_time is not None
            and now > req.first_token_time
            and not migrated
        ):
            # ONE span for the whole decode window — never one per step.
            # Migrated-away requests skip it: their decode continues on
            # the destination, which records its own window.
            tr.add(
                "request.decode",
                req.first_token_time,
                now,
                tokens=len(req.out_tokens),
                finish_reason=req.finish_reason or "",
            )
        keep = tr.sampled or violated or aborted or migrated
        if aborted and outcome == "finished":
            outcome = "aborted"
        tid = tr.finish(
            req.submit_time,
            now,
            keep,
            outcome=outcome,
            violated=bool(violated),
            prompt_tokens=len(req.prompt),
            tokens=len(req.out_tokens),
            preempt_s=round(getattr(req, "preempt_s", 0.0), 6),
        )
        req.trace = None
        req.trace_id = tid if keep else ""
        return req.trace_id

    def _finish_migrate_trace(
        self, req, t0: float, now: float, dest: str, outcome: str
    ) -> str:
        """Source-side close-out for a migrated-away stream: a
        ``request.migrate`` span over the handoff window
        [export-park, release], then the lifecycle root with
        outcome=migrated — ALWAYS retained (migration forensics: a
        cross-chip stream's source half must be fetchable whatever the
        sampling draw was). The destination's spans carry the same
        trace_id, so the two exports concatenate into one timeline."""
        if getattr(req, "trace", None) is None:
            return ""
        req.trace.add(
            "request.migrate", t0, now, dest=dest or "", outcome=outcome
        )
        req.trace.sampled = True
        return self._finish_request_trace(
            req, now, migrated=True, outcome=outcome
        )

    def _observe_kv_usage(self) -> None:
        alloc = self.engine.allocator
        total = max(1, alloc.num_pages - 1)
        ENGINE_KV_USAGE.labels(model=self.args.model).set(
            (total - alloc.available) / total
        )

    def _observe_step(self) -> None:
        """Mirror per-step scheduler observability after each engine
        step: decode-slot occupancy, the packed-step token histogram,
        and pad-waste byte increments (the engine keeps cumulative
        totals; a swap installs a fresh engine whose counters restart,
        so a backwards jump resets the mirror instead of under-counting
        forever)."""
        eng = self.engine
        m = self.args.model
        ENGINE_SLOT_OCCUPANCY.labels(model=m).set(
            sum(1 for s in eng._slots if s is not None)
            / max(1, eng.cfg.max_batch)
        )
        stats = getattr(eng, "last_step_stats", None)
        if stats is not None and stats.get("mode") == "packed":
            ENGINE_PACKED_TOKENS.labels(model=m).observe(stats["tokens"])

        def mirror_path_totals(totals, seen_map, counter):
            # one delta/reset discipline for every cumulative per-path
            # engine byte dict (a swap installs a fresh engine whose
            # counters restart, so a backwards jump resets the mirror
            # instead of under-counting forever)
            for path, total in totals.items():
                seen = seen_map.get(path, 0)
                if total > seen:
                    counter.labels(model=m, path=path).inc(total - seen)
                if total != seen:
                    seen_map[path] = total

        mirror_path_totals(
            getattr(eng, "pad_waste_bytes", {}),
            self._pad_waste_seen, ENGINE_PAD_WASTE_BYTES,
        )
        mirror_path_totals(
            getattr(eng, "step_h2d_bytes", {}),
            self._step_h2d_seen, ENGINE_STEP_H2D_BYTES,
        )

    def _run_follower(self) -> None:
        """Gang follower: replay the leader's compiled calls until it
        shuts down. Exceptions fail /health so the crash relay heals us."""
        from .multihost import follower_loop

        try:
            follower_loop(self.engine, self.sleeper)
            if self.watchdog is not None:
                # clean SHUTDOWN received: the leader is about to exit on
                # purpose; don't let its disappearance read as a death
                self.watchdog.stop()
        except Exception as e:
            logger.exception("follower loop failed")
            self.failure = f"{type(e).__name__}: {e}"

    def _fail_all(self, exc: Exception) -> None:
        for entry in self._pending:
            fut = entry[3]
            if not fut.done():
                fut.set_exception(exc)
        self._pending.clear()
        for fut in self._futures.values():
            if not fut.done():
                fut.set_exception(exc)
        self._futures.clear()
        self._fut_seq.clear()

    # -- API used by handlers (event-loop thread) ---------------------------

    def queue_depth(self) -> int:
        """Waiting + in-flight request count (the HPA pressure signal)."""
        eng = self.engine
        running = sum(1 for s in eng._slots if s is not None)
        return len(self._pending) + len(eng._waiting) + running

    def stats(self) -> Dict[str, Any]:
        """One-call instance stats row (GET /v1/stats): queue depth,
        arrival-rate EWMA, SLO attainment, goodput, per-cause aborts and
        actuation counts — exactly what the launcher's fleet rollup
        aggregates across instances without parsing Prometheus text.
        Cheap and lock-bounded: safe while sleeping or under load."""
        now = time.monotonic()
        with self._slo_mu:
            met, violated = self._slo_met, self._slo_violated
            judged = met + violated
            out = {
                "model": self.args.model,
                "device": dict(self._device_info),
                # the resolved implementation (engine.py:
                # resolve_attention_impl), never "auto"
                "attention_impl": self.engine.attention_impl,
                "queue_depth": self.queue_depth(),
                "arrival_rate_rps": round(self._arrival.rate(now), 6),
                "slo": {
                    "ttft_ms": self._slo_ttft_s * 1e3,
                    "tpot_ms": self._slo_tpot_s * 1e3,
                    "met": met,
                    "violated": violated,
                    "attainment": (
                        round(met / judged, 6) if judged else None
                    ),
                },
                "finished_requests": self._finished_requests,
                "generated_tokens": self._generated_tokens,
                "goodput_tokens": self._goodput_tokens,
                "aborted": dict(self._aborted),
                "actuations": dict(self._actuations),
                "uptime_s": round(now - self.started_at, 3),
                "is_sleeping": self.sleeper.is_sleeping,
                # zero-drain preemption accounting (docs/perf.md
                # "Zero-drain actuation"): lifetime preempt/resume/abort
                # counts plus the host bytes parked KV holds right now —
                # what the fleet harness reads to prove "zero swap
                # aborts" and what the launcher rollup aggregates
                "zero_drain": {
                    "enabled": self._zero_drain,
                    "preempted": self._zd_preempted,
                    "resumed": self._zd_resumed,
                    "aborted": self._zd_aborted,
                    "migrated": self._zd_migrated,
                    "parked_kv_bytes": max(0, self._zd_parked_bytes),
                },
                # live-migration ledger (docs/operations.md "Draining a
                # node without dropping streams"): per-role terminal
                # outcomes plus the in-flight fence — what the launcher's
                # drain loop polls and the fleet rollup aggregates
                "migration": {
                    **self._mig,
                    "in_flight": bool(self._migration),
                    "imported_claims": len(self._imported_claims),
                },
                # last-N SLO-violated exemplars (docs/tracing.md): each
                # row pairs a retained trace_id with its leg-duration
                # breakdown, so "attainment dropped — which leg?" is one
                # stats read + one /v1/traces fetch
                "slo_exemplars": list(self._slo_exemplars),
            }
        # cost-oracle summary (utils/costs.py): per-kind bandwidth EWMAs
        # + last-N prediction accuracy — the fleet harness scores oracle
        # accuracy from this row without a second endpoint, and the
        # launcher's fleet rollup carries it into ledger.costs
        out["costs"] = self.costs.summary()
        from ..utils import compile_cache

        out["compile_cache"] = compile_cache.stats()
        # the pool a start reloads its serving programs from: spill_hits
        # are this start's reloads, spill_errors the blobs that did not
        # load or fit and went back to the lazy jit
        out["exec_pool"] = self.exec_pool.describe()
        # the layer stack, the two kinds of KV state and the expert layers,
        # host-counted (engine.cache_stats): layer passes dispatched, pages
        # in use, bytes a token, ring bytes, positions that left a ring,
        # tokens and assignments through the experts
        out.update(self.engine.cache_stats())
        # what the scheduler thread spent its time on, part by part, since
        # the process started (docs/tracing.md "Scheduler phases")
        out["scheduler"] = tracing.phase_stats()
        # where the seconds from the OS starting this process to its
        # listener accepting went, stage by stage (docs/tracing.md
        # "Start-up stages and program compiles"); frozen from then on
        out["startup"] = tracing.startup_stats()
        out["hbm"] = self._hbm_rows()
        return out

    def _hbm_rows(self) -> List[Dict[str, Any]]:
        """Per local device: the bytes its allocator reports in use and the
        bytes of this engine's state (param + KV shards) placed on it,
        computed from shapes and shardings alone. Empty while asleep: the
        state is off the device, and a stats read must not re-create a
        released backend."""
        if self.sleeper.is_sleeping:
            return []
        import jax
        import numpy as np

        state = (self.engine.params, self.engine.pool.as_tuple())
        if state[0] is None:  # a sleep edge raced this read
            return []
        rows = {
            d.id: {
                "id": d.id,
                "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use"),
                "state_bytes": 0,
            }
            for d in jax.local_devices()
        }
        for leaf in jax.tree.leaves(state):
            shard = leaf.sharding.shard_shape(leaf.shape)
            nbytes = int(np.prod(shard)) * leaf.dtype.itemsize
            for d in leaf.sharding.addressable_devices:
                rows[d.id]["state_bytes"] += nbytes
        return list(rows.values())

    def submit(
        self,
        prompt: List[int],
        max_tokens: int,
        temperature: float,
        on_tokens: Optional[Any] = None,
        top_p: float = 1.0,
        stop_seqs: Any = (),
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        want_top_logprobs: bool = False,
        want_prompt_logprobs: bool = False,
        seed: "int | None" = None,
        ignore_eos: bool = False,
        logit_bias: "Dict[int, float] | None" = None,
        trace_ctx: "tracing.SpanContext | None" = None,
        stop_watch: Optional[Any] = None,
    ) -> concurrent.futures.Future:
        """Enqueue a request. `on_tokens(req, tokens)` — if given — fires on
        the engine thread once for every run of tokens the request is
        emitted: its first token, then what each drained decode chunk held
        for it (the streaming hook); keep it to an enqueue.
        `stop_watch(token) -> bool` is asked about every token and ends the
        request at the next one when it says True. ``trace_ctx`` is
        the client's ``traceparent`` (completions handlers): it forces a
        lifecycle trace even at --trace-requests 0 and parents it on the
        caller's span."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        if self.is_follower:
            fut.set_exception(
                RuntimeError(
                    "multi-host gang follower: requests are served by the "
                    "gang leader (process 0)"
                )
            )
            return fut
        if self.failure is not None:
            fut.set_exception(RuntimeError(self.failure))
            return fut
        now = time.monotonic()
        with self._slo_mu:
            # demand signal, stamped at the HTTP edge: the EWMA must see
            # offered load even when the engine is saturated or asleep
            self._arrival.observe(now)
        trace = None
        if tracing.enabled() and (
            trace_ctx is not None or tracing.request_sampling() > 0.0
        ):
            # frac 0 with no client traceparent: no collector, and every
            # downstream hook is a single `is None` check (byte-inert)
            trace = tracing.RequestTrace(
                sampled=trace_ctx is not None or tracing.sample_request(),
                parent=trace_ctx,
            )
        self._pending.append(
            (prompt, max_tokens, temperature, fut, on_tokens, top_p, stop_seqs,
             presence_penalty, frequency_penalty, want_top_logprobs,
             want_prompt_logprobs, seed, ignore_eos, logit_bias, now,
             trace, stop_watch)
        )
        self._new_work.set()
        ENGINE_QUEUE_DEPTH.labels(model=self.args.model).set(self.queue_depth())
        return fut

    def abort(self, fut: concurrent.futures.Future) -> None:
        """Client went away: stop generating for its request (vLLM's abort;
        decode cycles on a disconnected request are pure waste). Applied by
        the engine thread at the next loop iteration."""
        self._abort_q.append(fut)
        self._new_work.set()

    def sleep(self, level: int) -> Dict[str, Any]:
        pred: Optional[Dict[str, Any]] = None
        try:
            # price_sleep models the level-1 offload; a level-2 sleep
            # discards state (bytes_offloaded = 0), so it stays unpriced
            pred = self.price_sleep() if level == 1 else None
        except Exception:  # noqa: BLE001 — pricing must never block the verb
            pred = None
        with tracing.span(
            "engine.sleep", level=level, model=self.args.model
        ) as sp:
            if pred is not None:
                sp.set(
                    predicted_bytes=pred.get("predicted_bytes"),
                    predicted_s=pred.get("predicted_s"),
                )
            return self._sleep_impl(level, pred=pred)

    def _sleep_impl(
        self, level: int, pred: Optional[Dict[str, Any]] = None
    ) -> Dict[str, Any]:
        if self.is_follower:
            # a follower can't unilaterally leave the collective loop; the
            # leader's broadcast sleeps the whole gang
            return {
                "deferred": True,
                "reason": "gang follower; sleep is driven by the leader",
            }
        if level not in (1, 2):
            # validate BEFORE any broadcast: a bad level must 400 locally,
            # never reach followers (their replay would raise and kill the
            # follower loop, deadlocking the gang's next collective)
            raise ValueError("sleep level must be 1 or 2")
        with self._admin_lock():
            was_sleeping = self.sleeper.is_sleeping
            prev_level = self.sleeper.level
            parked_for_sleep = None
            #: park attempted but fell back (page-out failure): the
            #: offload then moves the full pool the prediction's peek
            #: excluded — the record must go unpriced, not score a
            #: false byte miss
            zd_sleep_fallback = False
            if (
                level == 1
                and not was_sleeping
                and self._zero_drain_parks()
                and self.engine.lockstep is None
            ):
                # zero-drain: page the live requests' KV out compactly
                # BEFORE the offload — the slept state is then
                # weights-only (the full, mostly-empty pool stops
                # occupying host bytes) and wake re-seats the bundle.
                # A park failure just keeps today's full-pool offload,
                # which already preserves in-flight requests across a
                # plain L1 sleep.
                parked_for_sleep = self._park_current(park_pending=False)
                if parked_for_sleep is not None:
                    self._runtime.parked = parked_for_sleep
                else:
                    zd_sleep_fallback = True
            if self.engine.lockstep is not None:
                if level >= 2:
                    raise ValueError(
                        "level-2 sleep is not supported for multi-host "
                        "gangs (followers cannot replay the reinit)"
                    )
                self.engine.lockstep.sleep(level, self.release_on_sleep)
            if self.release_on_sleep:
                # Device release destroys the PJRT client that owns the
                # pooled models' pinned-host state and every compiled
                # executable — a later pool hit would stream from dead
                # buffers. Drop everything client-owned while the client
                # is still alive: the model pool (next swap-in
                # cold-builds), the live executable-pool entries (spilled
                # copies survive where reload is trusted), the engine's
                # installed AOT table, and the last warmup task's results
                # dict, which pins the same client-owned executables.
                # Wake re-validates the executable pool.
                if len(self.model_pool):
                    self._free_pooled(
                        self.model_pool.drain(), "device release"
                    )
                # a still-running warmup (e.g. kicked by an in-flight
                # prefetch) must be fenced BEFORE the pool drop: left
                # alone, it would finish its compile after drop_live()
                # and re-pool an executable owned by the dead client
                lw = self._last_warmup
                if lw is not None:
                    lw.abort(drop_results=True)
                    lw.wait(5)
                self.exec_pool.drop_live()
                self.engine.clear_executables()
                self._last_warmup = None
            try:
                out = self.sleeper.sleep(
                    level, release=self.release_on_sleep
                )
            except Exception as sleep_exc:
                if (
                    parked_for_sleep is not None
                    and self._runtime.parked is parked_for_sleep
                ):
                    # a failed offload has no rollback (plain sleep is
                    # not transactional) and the engine's state is
                    # indeterminate: resolve the parked futures to a
                    # clean state_loss abort instead of stranding them
                    # forever, and give the engine its pool back in
                    # case it can still serve
                    self._runtime.parked = None
                    try:
                        if self.engine.kv_detached:
                            self.engine.rebuild_kv_pool()
                    except Exception:  # noqa: BLE001 — best effort
                        logger.warning(
                            "KV pool rebuild after a failed sleep "
                            "failed", exc_info=True,
                        )
                    self._abort_parked_bundle(
                        parked_for_sleep,
                        self.args.model,
                        f"preempted requests lost: level-1 offload "
                        f"failed ({type(sleep_exc).__name__}: "
                        f"{sleep_exc})",
                    )
                raise
            if (
                int(self.sleeper.level) == 2
                and getattr(self._runtime, "parked", None) is not None
            ):
                # a level-2 edge (direct or L1->L2 escalation) drops the
                # host state a parked bundle would resume against: abort
                # the preempted requests cleanly (state_loss), exactly
                # like the state they rode with
                b, self._runtime.parked = self._runtime.parked, None
                self._abort_parked_bundle(
                    b,
                    self.args.model,
                    "preempted requests lost: level-2 sleep discarded "
                    "the parked state",
                )
        if out.get("bytes_offloaded") and not was_sleeping:
            # per-mode wire bytes: payload bytes under --sleep-quant.
            # Guarded like the actuation count below — a re-sent sleep's
            # answer still describes the ORIGINAL offload's bytes, and
            # charging them again would double wire-byte telemetry.
            ENGINE_ACTUATION_BYTES.labels(
                mode=out.get("quant", "off") or "off", dir="d2h"
            ).inc(out["bytes_offloaded"])
        if not was_sleeping or self.sleeper.level != prev_level:
            # count state CHANGES only: a fresh sleep or an L1->L2
            # escalation (real state movement — the host copy drops), but
            # never an idempotent re-sent sleep, which moved nothing and
            # must not inflate the fleet rollup's actuations/hour
            self._bump_actuation("sleep")
            sleep_s = out.get("last_sleep_seconds", 0.0)
            if not was_sleeping:
                # phase=d2h is the pure transfer window — observed only
                # when a transfer actually ran (a level-2 sleep discards
                # state; a 0.0 sample would drag the window percentiles
                # toward zero); total is the whole verb
                if int(self.sleeper.level) == 1:
                    ENGINE_ACTUATION_SECONDS.labels(
                        kind="sleep", phase="d2h"
                    ).observe(
                        max(0.0, self.sleeper.stats.last_sleep_transfer_s)
                    )
                ENGINE_ACTUATION_SECONDS.labels(
                    kind="sleep", phase="total"
                ).observe(max(0.0, sleep_s))
            sleep_priced = (
                not was_sleeping
                and not self.is_gang
                and int(self.sleeper.level) == 1
                and not zd_sleep_fallback
            )
            self._record_actuation(
                "sleep",
                self.args.model,
                # an L1->L2 transition while already asleep is the
                # escalation edge (host copy dropped), not a client-
                # driven offload
                trigger="escalation" if was_sleeping else "client",
                tier="host" if int(self.sleeper.level) == 1 else "discard",
                # escalations moved no new bytes, gang offloads stage
                # per-shard, and L2 sleeps discard instead of offload:
                # all outside the pricing model, recorded unpriced
                pred=pred if sleep_priced else None,
                # a zero-drain park's KV page-out is part of what this
                # sleep moved: the prediction (price_sleep) counts it,
                # so the actual must too or byte_exact_frac lies
                actual_bytes=out.get("bytes_offloaded", 0)
                + (
                    parked_for_sleep.kv_nbytes if parked_for_sleep else 0
                ),
                # priced records score like-for-like against the pure
                # offload window price_sleep models (the quiesce and a
                # device release are outside it); the park's d2h window
                # joins it — same link, same prediction
                actual_s=(
                    self.sleeper.stats.last_sleep_transfer_s
                    + (
                        parked_for_sleep.pageout_s
                        if parked_for_sleep
                        else 0.0
                    )
                    if sleep_priced
                    else (0.0 if was_sleeping else sleep_s)
                ),
                extra=(
                    {"preempted": parked_for_sleep.preempted}
                    if parked_for_sleep
                    else None
                ),
            )
        self._publish_usage()
        return out

    def wake_up(self) -> Dict[str, Any]:
        pred: Optional[Dict[str, Any]] = None
        try:
            pred = self.price_wake()
        except Exception:  # noqa: BLE001 — pricing must never block the verb
            pred = None
        with tracing.span("engine.wake", model=self.args.model) as sp:
            if pred is not None:
                sp.set(
                    predicted_bytes=pred.get("predicted_bytes"),
                    predicted_s=pred.get("predicted_s"),
                )
            return self._wake_up_impl(pred=pred)

    def _wake_up_impl(
        self, pred: Optional[Dict[str, Any]] = None
    ) -> Dict[str, Any]:
        if self.is_follower:
            return {
                "deferred": True,
                "reason": "gang follower; wake is driven by the leader",
            }
        with self._admin_lock():
            was_sleeping = self.sleeper.is_sleeping
            was_l1 = (
                self.sleeper.level == 1
                and not getattr(self.sleeper, "_staged", None)
            )
            if self.engine.lockstep is not None and self.sleeper.is_sleeping:
                self.engine.lockstep.wake()
            if self.sleeper.level == 2:
                # KV state is gone: abort anything mid-generation before the
                # fresh state arrives, then rebuild params+pool in place.
                self._abort_engine_work(
                    "level-2 sleep discarded state",
                    RuntimeError("aborted by level-2 sleep (KV discarded)"),
                )
                eng = self.engine
                m = eng.cfg.model

                def reinit():
                    import jax

                    if self.checkpoint_dir:
                        # level-2 wake = reload from disk (the reference's
                        # L2 wake re-reads weights; README.md:16-26);
                        # load_params already lands on the mesh placement
                        from ..models import checkpoint as _ckpt

                        params = _ckpt.load_params(
                            self.checkpoint_dir, m, mesh=eng.mesh
                        )
                    elif self.hf_dir:
                        from ..models import hf as _hf

                        # streaming cold loader, straight onto the mesh
                        # placement (read of layer k+1 overlaps H2D of k)
                        params = _hf.load_params(
                            self.hf_dir, m, mesh=eng.mesh,
                            workers=getattr(
                                self.args, "load_workers", 0
                            ) or None,
                            max_inflight_bytes=max(
                                1,
                                getattr(
                                    self.args, "load_inflight_mib", 512
                                ),
                            ) << 20,
                        )
                    else:
                        from ..models.registry import init_params_placed

                        params = init_params_placed(
                            jax.random.key(self.args.seed), m, eng.mesh
                        )
                    # the engine's own build: every kind of sequence state
                    # the model has (pages, rings, recurrent state)
                    eng._create_pool()
                    return {"params": params, "kv": eng.pool.as_tuple()}

                out = self.sleeper.wake_up(reinit=reinit)
            else:
                out = self.sleeper.wake_up()
            # wake must not recompile: compiled programs are host-resident
            # and survive a plain sleep; after a device release the pool
            # re-validates (reinstalling spilled/pooled executables)
            # instead of recompiling
            self._reinstall_executables()
            if was_l1 and self.sleeper.stats.last_wake_bytes:
                # per-mode wire bytes the restore moved (payload bytes
                # under --sleep-quant, with the on-device dequant after)
                ENGINE_ACTUATION_BYTES.labels(
                    mode=self.sleeper.stats.last_quant or "off", dir="h2d"
                ).inc(self.sleeper.stats.last_wake_bytes)
            # zero-drain: the parked bundle's KV pages back into the
            # fresh pool and the preempted streams continue mid-decode
            # (a restore failure aborts them cleanly inside
            # _resume_parked; the engine serves either way)
            zd_resumed = zd_pagein = zd_dropped = 0
            zd_resume_s = 0.0
            zd_short = False
            if (
                was_sleeping
                and not self.sleeper.is_sleeping
                and getattr(self._runtime, "parked", None) is not None
            ):
                (
                    zd_resumed, zd_pagein, zd_resume_s, zd_dropped,
                    zd_short,
                ) = self._resume_parked(self._runtime)
        if was_sleeping:
            # a wake on an already-awake engine is a no-op, not an
            # actuation the fleet rollup should charge for
            self._bump_actuation("wake")
            wake_s = self.sleeper.stats.last_wake_seconds
            # phase=h2d is the transfer window (client reacquisition
            # excluded), observed only when a host payload actually
            # moved — a level-2 wake reinitializes instead; total is
            # the whole verb
            wake_transfer_s = self.sleeper.stats.last_wake_transfer_s
            if (was_l1 or self.is_gang) and wake_transfer_s > 0:
                # only when a host payload actually moved: an L2 wake
                # (incl. the gang case) reinitializes, and a 0.0 sample
                # would drag the transfer-window percentiles toward zero
                ENGINE_ACTUATION_SECONDS.labels(
                    kind="wake", phase="h2d"
                ).observe(wake_transfer_s)
            ENGINE_ACTUATION_SECONDS.labels(
                kind="wake", phase="total"
            ).observe(max(0.0, wake_s))
            # a page-in shortfall (dropped parked clients, or a restore
            # rolled back to the state_loss abort) makes the actual
            # bytes fall short of the (full-bundle) prediction: record
            # unpriced, like the other false-byte-miss classes (gang
            # wakes, L2 edges)
            priced = not self.is_gang and was_l1 and not zd_short
            self._record_actuation(
                "wake",
                self.args.model,
                trigger="client",
                tier="host" if was_l1 else "cold",
                # gang wakes restore per-process staged shards and L2
                # wakes reinitialize (actual h2d payload = 0): neither
                # matches the single-process L1 pricing, so both record
                # unpriced — a mismatched prediction would read as a
                # false byte-exactness miss
                pred=pred if priced else None,
                # parked-KV page-in is payload this wake moved: counted
                # like the park's page-out on the sleep record, so
                # predicted (price_wake) and actual stay byte-exact
                actual_bytes=(
                    self.sleeper.stats.last_wake_bytes + zd_pagein
                    if was_l1 or self.is_gang
                    else 0
                ),
                # a priced record scores the prediction like-for-like:
                # the transfer window (what price_wake models — client
                # reacquisition is deliberately outside it); unpriced
                # records keep the whole-verb wall
                actual_s=(
                    wake_transfer_s + zd_resume_s if priced else wake_s
                ),
                extra=(
                    {"resumed": zd_resumed} if zd_resumed else None
                ),
            )
        self._publish_usage()
        self._new_work.set()
        return out

    def shutdown(self) -> None:
        self._stop = True
        self._new_work.set()
        with self._prefetch_mu:
            t = self._prefetch_thread
            if t is not None and t.is_alive():
                self._prefetch_abort.set()
        if t is not None and t.is_alive():
            t.join(timeout=10)
        if not self.is_follower:
            # follower threads block inside the broadcast collective and
            # exit with the process (daemon); only the leader's loop joins
            self._thread.join(timeout=5)
        if self.engine.lockstep is not None:
            try:
                # under the lock: if the engine thread outlived the join
                # timeout (long compile mid-step), its frame broadcasts must
                # not interleave with the shutdown frame
                with self._lock:
                    self.engine.lockstep.shutdown()
            except Exception:
                logger.warning("lockstep shutdown broadcast failed", exc_info=True)
        if self.watchdog is not None:
            # only AFTER the SHUTDOWN broadcast: the broadcast is itself a
            # collective, so returning from it means every follower has the
            # frame — stopping the responder earlier would let a long
            # in-flight step turn an orderly stop into follower probers
            # reading the leader as dead. The leader's own monitor can't
            # misfire meanwhile: followers keep pinging until they process
            # SHUTDOWN and stop their watchdogs themselves.
            self.watchdog.stop()
        if self._publisher is not None:
            self._publisher.clear()


def _validate_messages(messages: Any) -> List[Dict[str, Any]]:
    if not isinstance(messages, list) or not messages:
        raise ValueError("messages must be a non-empty list")
    for m in messages:
        if not isinstance(m, dict) or "role" not in m or "content" not in m:
            raise ValueError("each message needs role and content")
        if not isinstance(m["content"], str):
            # OpenAI content-parts arrays (multimodal) are not supported;
            # they would also crash HF chat templates with a 500
            raise ValueError("message content must be a string")
    return messages


def _lifecycle_usage(req: Any) -> Dict[str, Any]:
    """Per-request lifecycle extras for the OpenAI usage block — the
    engine-side measurements an open-loop load harness needs without
    streaming (part of the completions API's reply): queue wait (submit ->
    first scheduled, the leg an actuation stall lands in) and decode
    TPOT (mean inter-token seconds after the first token)."""
    qw = None
    if req.first_sched_time is not None:
        qw = max(0.0, req.first_sched_time - req.submit_time)
    tpot = None
    n = len(req.out_tokens)
    if (
        req.first_token_time is not None
        and req.done_time is not None
        and n > 1
    ):
        tpot = max(0.0, (req.done_time - req.first_token_time) / (n - 1))
    out = {"queue_wait_s": qw, "decode_tpot_s": tpot}
    tid = getattr(req, "trace_id", "")
    if tid:
        # retained lifecycle trace (sampled or tail-kept): the handle a
        # client/harness passes to GET /v1/traces?trace_id=...
        out["trace_id"] = tid
    return out


def _finish_reason(service: "EngineService", req: Any) -> str:
    # the engine records why it finished (eos/stop-sequence vs budget);
    # fall back to the legacy eos check for requests that predate it
    if getattr(req, "finish_reason", ""):
        return req.finish_reason
    eos = service.engine.cfg.eos_token_id
    return (
        "stop" if req.out_tokens and req.out_tokens[-1] == eos else "length"
    )


class _CurrentTokenizer:
    """Tokenizer handle that always delegates to the service's *current*
    tokenizer, so handler closures built once at app construction follow
    model hot-swaps."""

    def __init__(self, service: EngineService) -> None:
        self._service = service

    def __getattr__(self, name: str):
        return getattr(self._service.tokenizer, name)


class _Mailbox:
    """Engine thread -> event loop, for streamed tokens: ``post`` queues an
    item for an ``asyncio.Queue`` and wakes the loop only if no wake-up is
    already pending, so the 64 deliveries of one drained chunk cost the
    scheduler thread one write to the loop's wake-up pipe, not 64 — each
    of those hands the GIL to the writer for one event's work while the
    device waits for the loop to go on. Nothing waits longer: the loop
    drains everything that queued before it ran."""

    def __init__(self) -> None:
        self._items: deque = deque()
        self._wake_pending = False

    def post(self, loop, q: asyncio.Queue, item: Any) -> None:
        # append BEFORE reading the flag; drain clears the flag BEFORE it
        # pops: an item that saw the flag set is seen by that drain
        self._items.append((q, item))
        if not self._wake_pending:
            self._wake_pending = True
            try:
                loop.call_soon_threadsafe(self._drain)
            except BaseException:
                # a closed loop: the next post must try again, not wait
                # for a drain that was never scheduled
                self._wake_pending = False
                raise

    def _drain(self) -> None:
        self._wake_pending = False
        while self._items:
            q, item = self._items.popleft()
            q.put_nowait(item)


def build_app(service: EngineService) -> web.Application:
    app = web.Application()

    async def listening(_app: web.Application) -> None:
        # the runner is set up and the listener is next: the process is
        # ready, and the start-up table (utils/tracing.py) is frozen
        tracing.startup_ready()

    app.on_startup.append(listening)
    mailbox = _Mailbox()
    # read per-request, never captured: both change on a model hot-swap
    tok = _CurrentTokenizer(service)

    def _vocab() -> int:
        return service.engine.cfg.model.vocab_size

    def _encode_prompt(prompt: Any) -> List[int]:
        if isinstance(prompt, list):
            return [int(t) for t in prompt]
        if isinstance(prompt, str):
            return tok.encode(prompt)
        raise ValueError("prompt must be a string or a list of token ids")

    def _chat_tokens(messages: Any) -> List[int]:
        msgs = _validate_messages(messages)
        try:
            return tok.chat_tokens(msgs)
        except ValueError:
            raise
        except Exception as e:
            # jinja TemplateError on role patterns the model's template
            # rejects, TypeError on content-parts arrays, ...: malformed
            # request input, not a server fault -> 400
            raise ValueError(f"chat template failed: {e}")

    async def health(request: web.Request) -> web.Response:
        if service.failure is not None:
            return web.json_response(
                {"status": "FAILED", "error": service.failure}, status=503
            )
        if service.degraded is not None:
            # healed-in-process failures (rolled-back swap): still serving
            # — 200, so no controller restarts us — but visibly degraded
            # for operators and the launcher
            return web.json_response(
                {"status": "DEGRADED", "reason": service.degraded}
            )
        return web.json_response({"status": "OK"})

    async def is_sleeping(request: web.Request) -> web.Response:
        # `is_sleeping` is the reference wire contract; `devices_released`
        # is the TPU-specific extra the launcher's chip-exclusivity probe
        # needs (sleeping-but-client-open still holds the chip).
        return web.json_response(
            {
                "is_sleeping": service.sleeper.is_sleeping,
                "devices_released": service.sleeper.devices_released,
            }
        )

    def _traced_call(request: web.Request, fn):
        """Blocking admin call on the executor, with the caller's remote
        ``traceparent`` (if any) as the current context inside it."""
        return tracing.run_traced(
            asyncio.get_running_loop(), request.headers, fn
        )

    async def sleep(request: web.Request) -> web.Response:
        level = int(request.query.get("level", "1"))
        try:
            info = await _traced_call(request, lambda: service.sleep(level))
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        return web.json_response(info)

    async def wake_up(request: web.Request) -> web.Response:
        info = await _traced_call(request, service.wake_up)
        return web.json_response(info)

    async def swap(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str) or not model:
            raise web.HTTPBadRequest(text="swap requires a 'model' string")
        ckpt = body.get("checkpoint_dir") or ""
        if not isinstance(ckpt, str):
            raise web.HTTPBadRequest(text="checkpoint_dir must be a string")
        rid = body.get("request_id") or ""
        if not isinstance(rid, str):
            raise web.HTTPBadRequest(text="request_id must be a string")
        try:
            info = await _traced_call(
                request, lambda: service.swap(model, ckpt, request_id=rid)
            )
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        except SwapRolledBack as e:
            # transactional rollback: the previous model serves again and
            # the target is still pooled — retryable, so 503 (not 500)
            return web.json_response(
                {
                    "error": str(e),
                    "rolled_back": True,
                    "model": service.args.model,
                },
                status=503,
            )
        return web.json_response(info)

    async def last_swap(request: web.Request) -> web.Response:
        # the launcher's timeout-recovery read: last committed swap (with
        # its request_id) + the degraded marker
        return web.json_response(
            {**service.last_swap, "degraded": service.degraded}
        )

    async def prefetch(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str) or not model:
            raise web.HTTPBadRequest(text="prefetch requires a 'model' string")
        ckpt = body.get("checkpoint_dir") or ""
        if not isinstance(ckpt, str):
            raise web.HTTPBadRequest(text="checkpoint_dir must be a string")
        try:
            info = await _traced_call(
                request, lambda: service.prefetch(model, ckpt)
            )
        except (ValueError, FileNotFoundError) as e:
            raise web.HTTPBadRequest(text=str(e))
        return web.json_response(info)

    async def prefetch_status(request: web.Request) -> web.Response:
        return web.json_response(service.prefetch_status())

    async def prefetch_abort(request: web.Request) -> web.Response:
        info = await asyncio.get_running_loop().run_in_executor(
            None, service.abort_prefetch
        )
        return web.json_response(info)

    async def models(request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [{"id": service.args.model, "object": "model"}],
            }
        )

    async def engine_stats(request: web.Request) -> web.Response:
        """JSON lifecycle stats (GET /v1/stats): the launcher's fleet
        rollup polls this instead of scraping+parsing /metrics."""
        return web.json_response(service.stats())

    async def costs_get(request: web.Request) -> web.Response:
        """GET /v1/costs: every candidate actuation priced before any
        byte moves (docs/operations.md "Pricing an actuation").
        ``?model=X[&checkpoint_dir=D]`` adds an arbitrary target to the
        candidate list. Pricing flattens weight trees, so it runs on the
        executor, never the event loop."""
        extras = []
        model = request.query.get("model", "")
        if model:
            extras.append(
                (model, request.query.get("checkpoint_dir", "") or "")
            )
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, lambda: service.costs_view(extras)
            )
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        return web.json_response(out)

    async def actuations_get(request: web.Request) -> web.Response:
        """GET /v1/actuations: the decision flight recorder's ring —
        ``?n=`` bounds the returned records (newest kept), ``?kind=``
        filters by actuation kind."""
        try:
            n = int(request.query.get("n", "0") or 0)
        except ValueError:
            raise web.HTTPBadRequest(text="n must be an integer")
        kind = request.query.get("kind") or None
        return web.json_response(service.actuations_view(n=n, kind=kind))

    async def metrics(request: web.Request) -> web.Response:
        from prometheus_client import generate_latest

        ENGINE_QUEUE_DEPTH.labels(model=service.args.model).set(
            service.queue_depth()
        )
        with service._slo_mu:
            # decayed to scrape time: after traffic stops the demand
            # signal visibly ramps down instead of freezing
            ENGINE_ARRIVAL_RATE.labels(model=service.args.model).set(
                service._arrival.rate(time.monotonic())
            )
        if service.engine.prefix_cache is not None:
            ENGINE_PREFIX_HIT_TOKENS.labels(model=service.args.model).set(
                service.engine.prefix_cache.hit_tokens
            )
        if service.engine.cfg.speculative_ngram > 0:
            ENGINE_SPEC_PROPOSED.labels(model=service.args.model).set(
                service.engine.spec_proposed
            )
            ENGINE_SPEC_ACCEPTED.labels(model=service.args.model).set(
                service.engine.spec_accepted
            )
        if service.engine.cfg.speculative_mtp:
            ENGINE_MTP_DRAFTED.labels(model=service.args.model).set(
                service.engine.mtp_drafted
            )
            ENGINE_MTP_ACCEPTED.labels(model=service.args.model).set(
                service.engine.mtp_accepted
            )
        pool = service.model_pool
        ENGINE_POOL_BYTES.set(pool.bytes_used)
        ENGINE_POOL_MODELS.set(len(pool))
        if pool.chunks is not None:
            # running counters — the scrape never re-sums entries
            ENGINE_POOL_TIER_BYTES.labels(tier="host").set(
                pool.chunks.host_bytes
            )
            ENGINE_POOL_TIER_BYTES.labels(tier="disk").set(
                pool.chunks.disk_bytes
            )
            cd = pool.chunks.describe()
            ENGINE_POOL_TIER_CHUNKS.labels(tier="host").set(
                cd["host_chunks"]
            )
            ENGINE_POOL_TIER_CHUNKS.labels(tier="disk").set(
                cd["disk_chunks"]
            )
            ENGINE_POOL_DEDUP_SAVED.set(pool.chunks.dedup_saved_bytes)
        ENGINE_EXEC_POOL_BYTES.set(service.exec_pool.bytes_used)
        ENGINE_EXEC_POOL_ENTRIES.set(len(service.exec_pool))
        return web.Response(
            body=generate_latest(),
            content_type="text/plain",
        )

    def _parse_stop(stop: Any) -> tuple:
        """OpenAI `stop`: a string, a list of strings, or token-id lists.
        Malformed values must surface as ValueError (-> HTTP 400).

        Returns (token_seqs, stop_texts). Token-id stops match in the
        engine; STRING stops match on decoded text in the response layer
        (tokenizer.TextStopStream / truncate_at_text_stop) — BPE does not
        round-trip decode→encode, and a stop string can start mid-token,
        so re-encoding strings into token sequences would miss matches."""
        if stop is None:
            return (), ()
        vocab = _vocab()
        if isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list):
            raise ValueError("stop must be a string or a list")
        seqs = []
        texts = []
        for s in stop:
            if isinstance(s, str):
                texts.append(s)
            elif isinstance(s, int):
                seqs.append((s % vocab,))
            elif isinstance(s, list):
                try:
                    seqs.append(tuple(int(t) % vocab for t in s))
                except (TypeError, ValueError) as e:
                    raise ValueError(f"invalid stop token list {s!r}") from e
            else:
                raise ValueError(f"invalid stop entry {s!r}")
        return tuple(s for s in seqs if s), tuple(t for t in texts if t)

    def _parse_generation(body: Dict[str, Any], tokens: List[int]):
        vocab = _vocab()
        tokens = [t % vocab for t in tokens]
        if not tokens:
            raise ValueError("empty prompt")
        try:
            mt = body.get("max_tokens")
            max_tokens = 16 if mt is None else int(mt)
            tv = body.get("temperature")
            temperature = 0.0 if tv is None else float(tv)
            top_p = float(
                1.0 if body.get("top_p") is None else body.get("top_p")
            )
        except (TypeError, ValueError) as e:
            raise ValueError(f"invalid generation parameter: {e}")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        from .engine import validate_logit_bias

        logit_bias = validate_logit_bias(body.get("logit_bias"), vocab)
        iev = body.get("ignore_eos")
        if iev is not None and not isinstance(iev, bool):
            raise ValueError(f"ignore_eos must be a bool, got {iev!r}")
        ignore_eos = bool(iev)
        sv = body.get("seed")
        if sv is not None and (isinstance(sv, bool) or not isinstance(sv, int)):
            raise ValueError(f"seed must be an integer, got {sv!r}")
        if sv is not None and not (-(2**63) <= sv < 2**63):
            # out-of-int64 seeds would overflow jax.random.key at
            # admission — inside the engine thread, not this request
            raise ValueError("seed must fit in a signed 64-bit integer")
        seed = None if sv is None else int(sv)
        if not (0.0 < top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        try:
            pp = body.get("presence_penalty")
            presence = 0.0 if pp is None else float(pp)
            fp = body.get("frequency_penalty")
            frequency = 0.0 if fp is None else float(fp)
        except (TypeError, ValueError) as e:
            raise ValueError(f"invalid penalty: {e}")
        for name, v in (("presence_penalty", presence), ("frequency_penalty", frequency)):
            if not (-2.0 <= v <= 2.0):
                raise ValueError(f"{name} must be in [-2, 2], got {v}")
        stop_seqs, stop_texts = _parse_stop(body.get("stop"))
        sti = body.get("stop_token_ids")
        if sti is not None:
            # vLLM's parameter name; matching is engine-level single-id
            # stops with OUR strip semantics (the matched token is removed
            # from the output, like every other stop here — vLLM keeps
            # non-special ids in the completion; docs/engine.md says so)
            if not isinstance(sti, list):
                raise ValueError("stop_token_ids must be a list of ints")
            extra = []
            for t in sti:
                if isinstance(t, bool) or not isinstance(t, int):
                    raise ValueError(
                        f"stop_token_ids entries must be ints, got {t!r}"
                    )
                if not (0 <= t < vocab):
                    # an id the model cannot emit: wrapping it onto an
                    # unrelated real token would truncate generations
                    # at random; reject instead
                    raise ValueError(
                        f"stop_token_ids entry {t} outside vocab [0, {vocab})"
                    )
                extra.append((t,))
            stop_seqs = stop_seqs + tuple(extra)
        # pre-validate everything add_request would reject, so streaming
        # requests fail with a 400 instead of an SSE error after headers
        # are out
        cfg = service.engine.cfg
        if len(tokens) + max_tokens > cfg.seq_len:
            raise ValueError(
                f"prompt+generation {len(tokens)}+{max_tokens} exceeds "
                f"max_seq_len {cfg.seq_len}"
            )
        from .kv_cache import PageAllocator

        need = PageAllocator.pages_needed(
            len(tokens) + max_tokens, cfg.page_size
        )
        if need > cfg.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{cfg.num_pages - 1}"
            )
        return (
            tokens, max_tokens, temperature, top_p, stop_seqs, stop_texts,
            presence, frequency, seed, ignore_eos, logit_bias,
        )

    async def _stream_sse(
        request: web.Request,
        tokens: List[int],
        max_tokens: int,
        temperature: float,
        top_p: float,
        stop_seqs: tuple,
        stop_texts: tuple,
        presence: float,
        frequency: float,
        make_chunk,
        seed=None,
        ignore_eos=False,
        logit_bias=None,
        trace_ctx=None,
        usage_chunk=None,
    ) -> web.StreamResponse:
        """OpenAI-style SSE stream: one `data: {json}` event per run of
        tokens the engine emits the request (its first token, then what
        each drained decode chunk held for it, all in `token_ids`),
        `data: [DONE]` terminator. A run crosses the engine-thread ->
        event-loop boundary as ONE item of the app's mailbox, which wakes
        this loop once for all the runs of a chunk (``_Mailbox``).

        Chunk text comes from an incremental detokenizer; stop STRINGS are
        matched here on the decoded text (held back until disambiguated)
        and end the stream early, aborting the in-flight generation.

        When the stream completes normally a final ``usage_chunk`` event
        precedes ``[DONE]``, carrying the lifecycle fields non-streaming
        responses already expose (queue_wait_s / decode_tpot_s /
        trace_id) — streamed requests are scoreable by the fleet harness
        too."""
        from .tokenizer import IncrementalDecoder, TextStopStream

        filt = TextStopStream(tok, stop_texts) if stop_texts else None
        dec = IncrementalDecoder(tok)
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def on_tokens(req, run: List[int]) -> None:
            mailbox.post(loop, q, (run, req.done))

        fut = service.submit(
            tokens, max_tokens, temperature, on_tokens=on_tokens,
            top_p=top_p, stop_seqs=stop_seqs,
            presence_penalty=presence, frequency_penalty=frequency,
            seed=seed, ignore_eos=ignore_eos, logit_bias=logit_bias,
            trace_ctx=trace_ctx,
        )
        afut = asyncio.ensure_future(asyncio.wrap_future(fut))
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
        )
        qtask: Optional[asyncio.Task] = None
        completed = False
        try:
            # inside the try: a disconnect cancelling this await must still
            # abort the in-flight generation
            await resp.prepare(request)
            index = 0
            while True:
                if qtask is None:
                    qtask = asyncio.ensure_future(q.get())
                done_set, _ = await asyncio.wait(
                    {qtask, afut}, return_when=asyncio.FIRST_COMPLETED
                )
                if qtask in done_set:
                    run, req_done = qtask.result()
                    qtask = None
                    if filt is not None:
                        # the filter tracks id<->text attribution through
                        # its hold-back window: every emission's ids are
                        # exactly the tokens whose decoded text it contains
                        text, ids, matched = "", [], False
                        for i, t in enumerate(run):
                            new, tids, matched = filt.push(t)
                            text += new
                            ids += tids
                            if matched:
                                # the request ended only if the match is
                                # at the run's last token: else abort it
                                req_done = req_done and i == len(run) - 1
                                break
                        if not matched and req_done:
                            tail, tids, matched = filt.flush()
                            text += tail
                            ids = ids + tids
                        if matched:
                            # everything before the stop flushes in one
                            # final chunk; text AND ids of the (possibly
                            # partial) stop content are suppressed together
                            if text:
                                payload = json.dumps(
                                    make_chunk(text, ids, index)
                                )
                                index += 1
                                await resp.write(
                                    f"data: {payload}\n\n".encode()
                                )
                            if not req_done:
                                service.abort(fut)
                            completed = req_done
                            break
                        if not text and not req_done:
                            continue  # held back: ids stay in the filter
                    else:
                        text = dec.push_run(run)
                        if req_done:
                            text += dec.flush()
                        ids = run
                    payload = json.dumps(make_chunk(text, ids, index))
                    index += 1
                    await resp.write(f"data: {payload}\n\n".encode())
                    if req_done:
                        completed = True
                        break
                elif afut.done():
                    # finished without a terminal token event: submit error,
                    # engine failure, or an abort — surface it as an SSE
                    # error event (headers are already gone)
                    exc = (
                        afut.exception()
                        if not afut.cancelled()
                        else RuntimeError("request aborted")
                    )
                    if exc is not None:
                        err = json.dumps({"error": str(exc)})
                        await resp.write(f"data: {err}\n\n".encode())
                    break
            if completed and usage_chunk is not None:
                # the future resolves right after the terminal token (the
                # engine loop resolves it in the same step); shield keeps
                # the finally's cancel from killing a racing completion
                req = None
                with contextlib.suppress(Exception):
                    req = await asyncio.wait_for(
                        asyncio.shield(afut), timeout=5.0
                    )
                if req is not None and getattr(req, "error", None) is None:
                    u = {
                        "prompt_tokens": len(req.prompt),
                        "completion_tokens": len(req.out_tokens),
                        "time_to_first_token_s": (
                            (req.first_token_time - req.submit_time)
                            if req.first_token_time
                            else None
                        ),
                        **_lifecycle_usage(req),
                    }
                    payload = json.dumps(usage_chunk(u))
                    await resp.write(f"data: {payload}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
        except (asyncio.CancelledError, ConnectionResetError):
            service.abort(fut)
            raise
        finally:
            if qtask is not None:
                qtask.cancel()
            afut.cancel()
        await resp.write_eof()
        return resp

    async def _await_generation(fut):
        try:
            return await asyncio.wrap_future(fut)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        except asyncio.CancelledError:
            # client disconnected: free the slot instead of decoding on
            service.abort(fut)
            raise

    def _parse_n(body: Dict[str, Any]) -> int:
        try:
            nv = body.get("n")
            n = 1 if nv is None else int(nv)
        except (TypeError, ValueError):
            raise web.HTTPBadRequest(text="n must be an integer")
        if not (1 <= n <= service.engine.cfg.max_batch):
            raise web.HTTPBadRequest(
                text=f"n must be in 1..{service.engine.cfg.max_batch}"
            )
        if body.get("stream") and n != 1:
            raise web.HTTPBadRequest(text="n > 1 is not supported with stream")
        return n

    def _top_dict(alts, n: int) -> Dict[str, float]:
        """OpenAI completions top_logprobs entry: decoded-token -> logprob.
        Distinct ids can decode to the same string (byte fallback,
        whitespace variants); keep the best logprob on collision."""
        out: Dict[str, float] = {}
        for tid, lp_ in alts[:n]:
            key = tok.decode([tid], skip_special=False)
            if key not in out or lp_ > out[key]:
                out[key] = lp_
        return out

    def _parse_logprobs_n(v: Any, field: str = "logprobs") -> int:
        """OpenAI completions `logprobs` / chat `top_logprobs`: false/true
        (sampled-token logprobs only) or an int = how many top
        alternatives per position. Bounded by the engine's compiled
        top-k. Validated BEFORE submission: a bad value must 400 without
        burning a full generation."""
        if v is None or isinstance(v, bool):
            return 0
        try:
            n = int(v)
        except (TypeError, ValueError):
            raise ValueError(f"{field} must be a bool or int, got {v!r}")
        limit = service.engine.cfg.logprobs_topk
        if n < 0 or n > limit:
            raise ValueError(
                f"{field} must be in [0, {limit}] (engine --logprobs-topk)"
            )
        return n

    def _text_stop_watcher(stop_texts: tuple):
        """Engine-thread callback that asks for early termination once the
        decoded text contains a stop string — without it, a non-streaming
        request with stops would decode to eos/max_tokens holding a batch
        slot, and only the response text would be truncated."""
        from .tokenizer import TextStopStream

        filt = TextStopStream(tok, stop_texts)
        return lambda t: filt.push(t)[2]

    async def _gather_n(
        n: int, tokens, max_tokens, temperature, top_p, stop_seqs,
        presence, frequency, stop_texts=(), want_alts=False,
        want_prompt_logprobs=False, seed=None, ignore_eos=False,
        logit_bias=None, trace_ctx=None,
    ):
        """n parallel submissions; abort every sibling if any fails or the
        client goes away (no orphan decode cycles). Prefix caching makes
        the 2nd..nth prompt prefill nearly free (the OpenAI `n` param)."""
        futs = [
            service.submit(
                tokens, max_tokens, temperature,
                top_p=top_p, stop_seqs=stop_seqs,
                presence_penalty=presence, frequency_penalty=frequency,
                stop_watch=(
                    _text_stop_watcher(stop_texts) if stop_texts else None
                ),
                want_top_logprobs=want_alts,
                # prompt scores are identical across siblings: only the
                # first bypasses the prefix cache and pays the forward;
                # the response copies them onto the other choices
                want_prompt_logprobs=want_prompt_logprobs and i == 0,
                # OpenAI n + seed: distinct samples per choice, but the
                # SET of choices is reproducible. Wrap into int64 so a
                # seed near the bound that _parse_generation accepted
                # can't overflow jax.random.key for i>0.
                seed=None if seed is None
                else ((seed + i + 2**63) % 2**64) - 2**63,
                ignore_eos=ignore_eos,
                logit_bias=logit_bias,
                # the client's traceparent traces choice 0 (whose usage
                # the response carries); siblings stay on the sampler
                trace_ctx=trace_ctx if i == 0 else None,
            )
            for i in range(n)
        ]
        try:
            return [await _await_generation(f) for f in futs]
        except BaseException:
            for f in futs:
                if not f.done():
                    service.abort(f)
            raise

    async def completions(request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        try:
            (
                tokens, max_tokens, temperature, top_p, stop_seqs,
                stop_texts, presence, frequency, seed, ignore_eos,
                logit_bias,
            ) = _parse_generation(body, _encode_prompt(body.get("prompt")))
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        raw_prompt = body.get("prompt")
        # a body's "model" that is not the model being served is a 400,
        # so a router never silently gets the wrong weights
        try:
            service.check_request_model(body.get("model"))
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        resp_model = body.get("model") or service.args.model
        trace_ctx = tracing.context_from_headers(request.headers)

        n = _parse_n(body)
        try:
            logprobs_n = _parse_logprobs_n(body.get("logprobs"), "logprobs")
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        echo = bool(body.get("echo"))
        if body.get("stream"):
            if logprobs_n > 0:
                raise web.HTTPBadRequest(
                    text="integer logprobs is not supported with stream"
                )
            if echo:
                raise web.HTTPBadRequest(
                    text="echo is not supported with stream"
                )

            def chunk(text: str, ids: List[int], index: int) -> Dict[str, Any]:
                return {
                    "object": "text_completion",
                    "model": resp_model,
                    "choices": [
                        {"index": 0, "text": text, "token_ids": ids}
                    ],
                }

            def usage_chunk(usage: Dict[str, Any]) -> Dict[str, Any]:
                return {
                    "object": "text_completion",
                    "model": resp_model,
                    "choices": [],
                    "usage": usage,
                }

            return await _stream_sse(
                request, tokens, max_tokens, temperature, top_p, stop_seqs,
                stop_texts, presence, frequency, chunk, seed=seed,
                ignore_eos=ignore_eos, logit_bias=logit_bias,
                trace_ctx=trace_ctx,
                usage_chunk=usage_chunk,
            )

        reqs = await _gather_n(
            n, tokens, max_tokens, temperature, top_p, stop_seqs,
            presence, frequency, stop_texts, want_alts=logprobs_n > 0,
            want_prompt_logprobs=echo and bool(body.get("logprobs")),
            seed=seed, ignore_eos=ignore_eos, logit_bias=logit_bias,
            trace_ctx=trace_ctx,
        )
        req = reqs[0]
        ttft = (
            (req.first_token_time - req.submit_time)
            if req.first_token_time
            else None
        )
        from .tokenizer import truncate_at_text_stop

        choices = []
        total_completion = 0
        for i, r in enumerate(reqs):
            kept, kept_lps, text, matched = truncate_at_text_stop(
                tok, r.out_tokens, r.out_logprobs, stop_texts
            )
            total_completion += len(kept)
            choice = {
                "index": i,
                "token_ids": kept,
                "text": (
                    # echo returns the prompt the client sent: a text
                    # prompt verbatim (re-decoding would render the
                    # tokenizer's auto-added BOS), a token-id prompt as
                    # its literal decode, specials included (distinct
                    # special ids must not silently vanish)
                    (
                        raw_prompt
                        if isinstance(raw_prompt, str)
                        else tok.decode(tokens, skip_special=False)
                    )
                    + text
                    if echo
                    else text
                ),
                "finish_reason": (
                    "stop" if matched else _finish_reason(service, r)
                ),
            }
            if body.get("logprobs"):
                # OpenAI echo+logprobs: the arrays cover prompt tokens
                # too (first entry null — nothing precedes it)
                lp_tokens = (tokens + kept) if echo else kept
                lp_vals = (
                    (reqs[0].prompt_logprobs + kept_lps)
                    if echo
                    else kept_lps
                )
                choice["logprobs"] = {
                    "tokens": lp_tokens,
                    "token_logprobs": lp_vals,
                }
                if logprobs_n > 0:
                    tops = [
                        _top_dict(alts, logprobs_n)
                        for alts in r.out_top_logprobs[: len(kept)]
                    ]
                    if echo:
                        tops = [{} for _ in tokens] + tops
                    choice["logprobs"]["top_logprobs"] = tops
            choices.append(choice)
        return web.json_response(
            {
                "object": "text_completion",
                "model": resp_model,
                "choices": choices,
                "usage": {
                    "prompt_tokens": len(tokens),
                    "completion_tokens": total_completion,
                    "time_to_first_token_s": ttft,
                    **_lifecycle_usage(req),
                },
            }
        )

    async def chat_completions(request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        try:
            (
                tokens, max_tokens, temperature, top_p, stop_seqs,
                stop_texts, presence, frequency, seed, ignore_eos,
                logit_bias,
            ) = _parse_generation(body, _chat_tokens(body.get("messages")))
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        try:
            service.check_request_model(body.get("model"))
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        resp_model = body.get("model") or service.args.model
        trace_ctx = tracing.context_from_headers(request.headers)
        n = _parse_n(body)
        try:
            top_n = (
                _parse_logprobs_n(body.get("top_logprobs"), "top_logprobs")
                if body.get("logprobs")
                else 0
            )
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        if body.get("stream"):
            if top_n > 0:
                raise web.HTTPBadRequest(
                    text="top_logprobs is not supported with stream"
                )

            def chunk(text: str, ids: List[int], index: int) -> Dict[str, Any]:
                delta: Dict[str, Any] = {"content": text}
                if index == 0:
                    delta["role"] = "assistant"
                return {
                    "object": "chat.completion.chunk",
                    "model": resp_model,
                    "choices": [{"index": 0, "delta": delta}],
                }

            def usage_chunk(usage: Dict[str, Any]) -> Dict[str, Any]:
                return {
                    "object": "chat.completion.chunk",
                    "model": resp_model,
                    "choices": [],
                    "usage": usage,
                }

            return await _stream_sse(
                request, tokens, max_tokens, temperature, top_p, stop_seqs,
                stop_texts, presence, frequency, chunk, seed=seed,
                ignore_eos=ignore_eos, logit_bias=logit_bias,
                trace_ctx=trace_ctx,
                usage_chunk=usage_chunk,
            )

        reqs = await _gather_n(
            n, tokens, max_tokens, temperature, top_p, stop_seqs,
            presence, frequency, stop_texts, want_alts=top_n > 0, seed=seed,
            ignore_eos=ignore_eos, logit_bias=logit_bias,
            trace_ctx=trace_ctx,
        )
        from .tokenizer import truncate_at_text_stop

        choices = []
        total_completion = 0
        for i, r in enumerate(reqs):
            kept, kept_lps, text, matched = truncate_at_text_stop(
                tok, r.out_tokens, r.out_logprobs, stop_texts
            )
            total_completion += len(kept)
            choice = {
                "index": i,
                "message": {
                    "role": "assistant",
                    "content": text,
                    "token_ids": kept,
                },
                "finish_reason": (
                    "stop" if matched else _finish_reason(service, r)
                ),
            }
            if body.get("logprobs"):
                # OpenAI chat logprobs shape: per-token entries with
                # optional top_logprobs alternatives
                choice["logprobs"] = {
                    "content": [
                        {
                            "token": tok.decode([tid], skip_special=False),
                            "logprob": lp,
                            "top_logprobs": [
                                {
                                    "token": tok.decode(
                                        [aid], skip_special=False
                                    ),
                                    "logprob": alp,
                                }
                                for aid, alp in alts[:top_n]
                            ],
                        }
                        for tid, lp, alts in zip(
                            kept, kept_lps, r.out_top_logprobs[: len(kept)]
                        )
                    ]
                }
            choices.append(choice)
        return web.json_response(
            {
                "object": "chat.completion",
                "model": resp_model,
                "choices": choices,
                "usage": {
                    "prompt_tokens": len(tokens),
                    "completion_tokens": total_completion,
                    **_lifecycle_usage(reqs[0]),
                },
            }
        )

    app.router.add_get("/health", health)
    app.router.add_get("/is_sleeping", is_sleeping)
    app.router.add_post("/sleep", sleep)
    app.router.add_post("/wake_up", wake_up)
    async def faults_get(request: web.Request) -> web.Response:
        return web.json_response(faults.describe())

    async def faults_arm(request: web.Request) -> web.Response:
        """Arm fault-injection points at runtime (the test / fault-drill
        surface; utils/faults.py): {"spec": "swap.h2d=fail:1,..."}."""
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        spec = body.get("spec")
        if not isinstance(spec, str) or not spec:
            raise web.HTTPBadRequest(text="faults requires a 'spec' string")
        try:
            faults.arm_spec(spec)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        return web.json_response(faults.describe())

    async def faults_reset(request: web.Request) -> web.Response:
        faults.reset()
        return web.json_response(faults.describe())

    async def parked_export(request: web.Request) -> web.Response:
        """GET /v1/parked/{model}: park every live stream and export the
        bundle wire document (docs/engine.md "/v1/parked"). 409 when a
        precondition refuses with nothing displaced; 500 when the export
        leg failed AFTER the park — the streams already resumed locally."""
        model = request.match_info["model"]
        try:
            info = await _traced_call(
                request, lambda: service.export_parked(model)
            )
        except MigrationRejected as e:
            raise web.HTTPConflict(text=str(e))
        except MigrationFailed as e:
            raise web.HTTPInternalServerError(text=str(e))
        return web.json_response(info)

    async def parked_import(request: web.Request) -> web.Response:
        """POST /v1/parked: seat an exported bundle. 400 on a corrupt
        document (wire version, KV chunk digests), 409 on identity or
        capacity refusal (destination untouched), 500 on a seat failure
        (destination rolled back clean) or the drilled lost-ack —
        retrying the SAME document is safe: the stored ack replays."""
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        try:
            info = await _traced_call(
                request, lambda: service.import_parked(body)
            )
        except MigrationRejected as e:
            raise web.HTTPConflict(text=str(e))
        except MigrationFailed as e:
            raise web.HTTPInternalServerError(text=str(e))
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        return web.json_response(info)

    async def parked_release(request: web.Request) -> web.Response:
        """POST /v1/parked/release: commit the handoff (import acked) —
        spends the fence; a second release, or a release after abort, is
        a 409 (double-resume refusal)."""
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        token = body.get("fence_token")
        if not isinstance(token, str) or not token:
            raise web.HTTPBadRequest(
                text="release requires a 'fence_token' string"
            )
        dest = body.get("dest") or ""
        claims = body.get("claims") or {}
        if not isinstance(dest, str) or not isinstance(claims, dict):
            raise web.HTTPBadRequest(
                text="'dest' must be a string and 'claims' an object"
            )
        try:
            info = await _traced_call(
                request,
                lambda: service.release_parked(
                    token, dest=dest, claims=claims
                ),
            )
        except MigrationRejected as e:
            raise web.HTTPConflict(text=str(e))
        return web.json_response(info)

    async def parked_abort(request: web.Request) -> web.Response:
        """POST /v1/parked/abort: roll the handoff back (import failed /
        destination gone) — spends the fence and resumes the parked
        streams locally."""
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(text="invalid JSON body")
        token = body.get("fence_token")
        if not isinstance(token, str) or not token:
            raise web.HTTPBadRequest(
                text="abort requires a 'fence_token' string"
            )
        try:
            info = await _traced_call(
                request, lambda: service.abort_migration(token)
            )
        except MigrationRejected as e:
            raise web.HTTPConflict(text=str(e))
        return web.json_response(info)

    async def parked_claim(request: web.Request) -> web.Response:
        """GET /v1/parked/claims/{claim_id}: one migrated-in stream's
        progress (long-poll with ?wait_s= and ?have=) — what the source's
        proxy watchers consume."""
        cid = request.match_info["claim_id"]
        try:
            wait_s = float(request.query.get("wait_s", "0"))
            have = int(request.query.get("have", "-1"))
        except ValueError:
            raise web.HTTPBadRequest(text="wait_s/have must be numeric")
        try:
            info = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: service.claim_view(cid, wait_s=wait_s, have=have),
            )
        except ValueError as e:
            raise web.HTTPNotFound(text=str(e))
        return web.json_response(info)

    async def parked_claim_abort(request: web.Request) -> web.Response:
        """DELETE /v1/parked/claims/{claim_id}: the source proxy's
        client dropped — abort the migrated-in stream on this
        (destination) instance too."""
        cid = request.match_info["claim_id"]
        try:
            info = await asyncio.get_running_loop().run_in_executor(
                None, lambda: service.abort_claim(cid)
            )
        except ValueError as e:
            raise web.HTTPNotFound(text=str(e))
        return web.json_response(info)

    async def traces(request: web.Request) -> web.Response:
        """Export this process's span ring buffer: Chrome trace-event JSON
        (Perfetto-loadable, the default) or ``?format=tree`` (human);
        ``?trace_id=`` filters to one trace, ``?clear=1`` drains after
        export (docs/tracing.md)."""
        status, body, ctype = tracing.export_http(
            request.query.get("format", "chrome"),
            trace_id=request.query.get("trace_id") or None,
            clear=request.query.get("clear") in ("1", "true"),
        )
        return web.Response(status=status, text=body, content_type=ctype)

    async def profile_start(request: web.Request) -> web.Response:
        log_dir, python_tracer = "", False
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:
                raise web.HTTPBadRequest(text="invalid JSON body")
            log_dir = body.get("log_dir") or ""
            if not isinstance(log_dir, str):
                raise web.HTTPBadRequest(text="log_dir must be a string")
            python_tracer = body.get("python_tracer", False)
            if not isinstance(python_tracer, bool):
                raise web.HTTPBadRequest(
                    text="python_tracer must be true or false"
                )
        try:
            info = await asyncio.get_running_loop().run_in_executor(
                None, lambda: service.start_profile(log_dir, python_tracer)
            )
        except ProfileConflict as e:
            raise web.HTTPConflict(text=str(e))
        except Exception as e:  # noqa: BLE001 — profiler backend failures
            raise web.HTTPInternalServerError(text=f"start_trace: {e}")
        return web.json_response(info)

    async def profile_stop(request: web.Request) -> web.Response:
        try:
            info = await asyncio.get_running_loop().run_in_executor(
                None, service.stop_profile
            )
        except ProfileConflict as e:
            raise web.HTTPConflict(text=str(e))
        except Exception as e:  # noqa: BLE001 — profiler backend failures
            raise web.HTTPInternalServerError(text=f"stop_trace: {e}")
        return web.json_response(info)

    async def profile_status(request: web.Request) -> web.Response:
        return web.json_response(service.profile_status())

    app.router.add_post("/v1/swap", swap)
    app.router.add_get("/v1/swap", last_swap)
    app.router.add_get("/v1/faults", faults_get)
    app.router.add_post("/v1/faults", faults_arm)
    app.router.add_delete("/v1/faults", faults_reset)
    app.router.add_post("/v1/prefetch", prefetch)
    app.router.add_get("/v1/prefetch", prefetch_status)
    app.router.add_delete("/v1/prefetch", prefetch_abort)
    app.router.add_post("/v1/parked", parked_import)
    app.router.add_post("/v1/parked/release", parked_release)
    app.router.add_post("/v1/parked/abort", parked_abort)
    app.router.add_get("/v1/parked/claims/{claim_id}", parked_claim)
    app.router.add_delete("/v1/parked/claims/{claim_id}", parked_claim_abort)
    app.router.add_get("/v1/parked/{model}", parked_export)
    app.router.add_get("/v1/traces", traces)
    app.router.add_post("/v1/profile", profile_start)
    app.router.add_delete("/v1/profile", profile_stop)
    app.router.add_get("/v1/profile", profile_status)
    app.router.add_get("/v1/models", models)
    app.router.add_get("/v1/stats", engine_stats)
    app.router.add_get("/v1/costs", costs_get)
    app.router.add_get("/v1/actuations", actuations_get)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/chat/completions", chat_completions)

    if os.environ.get("FMA_DEBUG_ENDPOINTS") == "1":
        # test-server role (SURVEY §4): crash induction for the
        # stopped-instance-recovery e2e (the reference kills its test server
        # the same way; the sentinel must see a real process death)
        async def debug_crash(request: web.Request) -> web.Response:
            import threading

            threading.Timer(0.1, lambda: os._exit(17)).start()
            return web.json_response({"crashing": True})

        app.router.add_post("/debug/crash", debug_crash)
    return app


def run_server(args: argparse.Namespace) -> None:
    """Blocking server main (the child process body)."""
    logging.basicConfig(level=logging.INFO)
    # armed here, not only by the launcher's preload: a stand-alone server
    # and a launcher child cache alike
    from jax._src import xla_bridge

    from ..utils import compile_cache

    # engine.start opens here, back-dated to the process's start; whether
    # the entry point already made the backend's client (the benchmark's
    # child does, to check its platform) says which stage holds those seconds
    tracing.startup_begin(
        xla_bridge.backends_are_initialized(),
        model=args.model,
        pid=os.getpid(),
    )
    logger.info("compile cache at %s", compile_cache.arm() or "(none)")
    service = EngineService(args)
    # built and about to answer /health: a compile from here on is one a
    # request waits for, and the log names it
    compile_cache.serving()
    # closed by the app's on_startup hook (build_app), with the table
    tracing.stage("start.listen").__enter__()
    app = build_app(service)
    try:
        web.run_app(
            app, host=args.host, port=args.port, print=None, handle_signals=True
        )
    finally:
        service.shutdown()


def main(argv: Optional[List[str]] = None) -> None:
    args = make_arg_parser().parse_args(argv)
    validate_parsed_args(args)
    run_server(args)


# interpreter, jax, aiohttp and the package are in: start.import ends here
tracing.server_imported()

if __name__ == "__main__":
    main()
