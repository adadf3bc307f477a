#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, at
TinyLlama-1.1B's published widths and depth (seeded random weights; the
machine has no network), and checks what comes out by the repo's own means:

  1. kernels   every Pallas kernel, compiled for the chip (``tpu_custom_call``
               in the lowered text), against the XLA reference implementation
               at TinyLlama (32/4/64) and Llama-3-8B (32/8/128) head shapes,
               page 16, context 1024;
  2. server    ``python -m llm_d_fast_model_actuation_tpu.engine.server
               --model tinyllama-1.1b`` with default flags: a handful of
               greedy /v1/completions (two prompt lengths, one streamed, one
               concurrent pair), level-1 sleep into pinned_host, wake, the
               same tokens;
  3. launcher  ``python -m llm_d_fast_model_actuation_tpu.launcher.main``
               without mock chips: two models time-share the one chip —
               instance A serves and sleeps with device release, instance B
               (bench-1b) is created on the same chip and serves, A wakes
               and returns the same tokens.

``--chips 4`` runs ONLY the four-chip phase: ``engine.server --model
llama3-8b --tensor-parallel-size 4`` under ``pallas`` (the shard_map kernels)
against ``grouped`` on the same mesh — greedy tokens and their logprobs,
token for token up to the end or to a demonstrated argmax near-tie (random
weights have near-flat logits) — plus a check that every chip holds about
a quarter of the parameter and KV bytes.

It never falls back: a child that does not report a ``tpu`` device fails the
run, and any failed phase makes it exit non-zero. The parent never
initializes a JAX backend (on TPU a process that has one holds the chip);
every phase that needs the chip runs in a child, one after another. Earlier
stdout lines are per-phase JSON; the LAST line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the values the engine child reported.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
PKG = "llm_d_fast_model_actuation_tpu"
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

#: |kernel - reference| bound on attention outputs of magnitude O(1): bf16
#: inputs and outputs (2^-8 relative) plus the MXU's single-pass bf16
#: products inside both the kernel and the reference. A wrong page, mask or
#: head mapping is off by O(1).
KERNEL_ATOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one run drives. The defaults are the chip run; the CPU rehearsal
    (tests/test_chip_smoke_rehearsal.py) passes tiny sizes and ``platform="cpu"``."""

    #: the platform every child must report
    platform: str = "tpu"
    model: str = "tinyllama-1.1b"
    second_model: str = "bench-1b"
    #: engine options appended for every server / instance
    engine_options: str = ""
    launcher_args: Tuple[str, ...] = ()
    #: (heads, kv_heads, head_dim) per kernel check
    head_shapes: Tuple[Tuple[int, int, int], ...] = ((32, 4, 64), (32, 8, 128))
    page_size: int = 16
    context: int = 1024
    #: Pallas interpreter mode for the kernel phase: only the CPU rehearsal
    interpret: bool = False
    prompt_lens: Tuple[int, int] = (5, 100)
    max_tokens: int = 16
    ready_timeout_s: float = 900.0
    # -- the four-chip phase
    tp_model: str = "llama3-8b"
    tp: int = 4
    #: (implementation under test, what it is compared with)
    tp_impls: Tuple[str, str] = ("pallas", "grouped")


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- processes and HTTP (stdlib only: the parent stays off jax) ---------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    return env


class Child:
    """A child process with its output in a log file; always stopped."""

    def __init__(
        self, name: str, argv: Sequence[str],
        also_kill: Optional[List[int]] = None,
    ) -> None:
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        #: pids (filled in by the caller as they appear) of process groups
        #: to kill with the child: its own children that left its group
        self.also_kill = also_kill if also_kill is not None else []
        self.proc = subprocess.Popen(
            list(argv), env=child_env(), cwd=ROOT, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def log_tail(self, nbytes: int = 4000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - nbytes))
            return f.read().decode(errors="replace")

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        for pid in [self.proc.pid, *self.also_kill]:  # whatever is left
            try:
                os.killpg(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        self._log.close()

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def http(
    method: str, url: str, body: Any = None, timeout: float = 600.0
) -> Any:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"{method} {url} -> {e.code}: {e.read().decode(errors='replace')}"
        ) from None
    return json.loads(raw) if raw else None


def wait_healthy(
    url: str, child: Child, timeout: float, dead=lambda: None
) -> float:
    """Seconds until ``url`` answered 200; fails as soon as the child is
    seen dead, or ``dead()`` returns the story of whatever else died."""
    t0 = time.monotonic()
    last: Any = None
    while time.monotonic() - t0 < timeout:
        if child.proc.poll() is not None:
            raise RuntimeError(
                f"{child.name} exited rc={child.proc.returncode} before "
                f"{url} was healthy:\n{child.log_tail()}"
            )
        story = dead()
        if story:
            raise RuntimeError(f"{url} will not come up: {story}")
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                if resp.status == 200:
                    return time.monotonic() - t0
        except (urllib.error.URLError, OSError) as e:
            last = e
        time.sleep(0.5)
    raise TimeoutError(
        f"{url} not healthy after {timeout}s ({last}):\n{child.log_tail()}"
    )


def prompt_of(n: int, vocab_floor: int = 200) -> List[int]:
    return [1 + (7 * i) % (vocab_floor - 1) for i in range(n)]


def greedy(prompt: List[int], max_tokens: int, **extra: Any) -> Dict[str, Any]:
    """A /v1/completions body: greedy, fixed length (eos ignored)."""
    return {"prompt": prompt, "max_tokens": max_tokens, "temperature": 0,
            "ignore_eos": True, **extra}


def complete(base: str, prompt: List[int], max_tokens: int) -> List[int]:
    out = http("POST", base + "/v1/completions", greedy(prompt, max_tokens))
    ids = out["choices"][0]["token_ids"]
    if len(ids) != max_tokens or not all(isinstance(t, int) for t in ids):
        raise RuntimeError(f"malformed completion: {out}")
    return ids


def complete_scored(
    base: str, prompt: List[int], max_tokens: int
) -> Dict[str, List[Any]]:
    """Greedy tokens with their logprobs."""
    out = http(
        "POST", base + "/v1/completions",
        greedy(prompt, max_tokens, logprobs=True),
    )
    choice = out["choices"][0]
    lps = choice["logprobs"]["token_logprobs"]
    if len(choice["token_ids"]) != max_tokens or len(lps) != max_tokens:
        raise RuntimeError(f"malformed completion: {out}")
    return {"tokens": choice["token_ids"], "logprobs": lps}


def score_token(base: str, context: List[int], token: int) -> float:
    """The logprob this server gives ``token`` after ``context`` (echo with
    logprobs scores a prompt's own tokens)."""
    out = http(
        "POST", base + "/v1/completions",
        greedy(context + [token], 1, echo=True, logprobs=True),
    )
    return out["choices"][0]["logprobs"]["token_logprobs"][len(context)]


#: logprob slack between two attention implementations of one model: bf16
#: matmuls reduced in different orders through every layer
IMPL_LOGPROB_TOL = 0.1


def compare_impls(
    a: Dict[str, List[Any]], b: Dict[str, List[Any]], score_in_b
) -> Dict[str, Any]:
    """Implementation ``a``'s greedy run of one prompt against the
    reference ``b``'s. They must agree token for token, with logprobs
    within ``IMPL_LOGPROB_TOL``, up to the end or to an argmax near-tie:
    a position where the reference itself scores ``a``'s token
    (``score_in_b(i)``) within the tolerance of its own. Past a tie that
    fell the other way the contexts differ and nothing further can be
    compared (random weights have near-flat logits, so ties are common).
    ``ok`` says whether that held; the measured slack is reported either
    way."""
    n = len(a["tokens"])
    agree = next(
        (i for i in range(n) if a["tokens"][i] != b["tokens"][i]), n
    )
    lp_diff = max(
        (abs(a["logprobs"][i] - b["logprobs"][i]) for i in range(agree)),
        default=0.0,
    )
    out: Dict[str, Any] = {
        "tokens_agreeing": agree, "of": n,
        "max_logprob_diff": round(lp_diff, 4),
        "ok": lp_diff <= IMPL_LOGPROB_TOL,
    }
    if agree < n:
        gap = b["logprobs"][agree] - score_in_b(agree)
        out["tie_gap"] = round(gap, 4)
        out["ok"] = out["ok"] and gap <= IMPL_LOGPROB_TOL
    return out


def complete_streamed(
    base: str, prompt: List[int], max_tokens: int
) -> Tuple[List[int], float]:
    """(token ids, seconds to the first streamed token)."""
    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps(greedy(prompt, max_tokens, stream=True)).encode(),
        headers={"Content-Type": "application/json"},
    )
    ids: List[int] = []
    first: Optional[float] = None
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=600) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            event = json.loads(line[len("data: "):])
            if "error" in event:
                raise RuntimeError(f"stream error: {event}")
            for choice in event.get("choices", []):
                if first is None and choice["token_ids"]:
                    first = time.monotonic() - t0
                ids += choice["token_ids"]
    if len(ids) != max_tokens or first is None:
        raise RuntimeError(f"streamed {len(ids)} of {max_tokens} tokens")
    return ids, first


def check_stats(plan: Plan, stats: Dict[str, Any], count: int) -> Dict[str, Any]:
    """The device an engine child reported, held to the plan: no fallback."""
    dev = stats["device"]
    if dev["platform"] != plan.platform or dev["count"] != count:
        raise RuntimeError(
            f"engine reports device {dev}; this run needs {count} "
            f"{plan.platform} device(s)"
        )
    if plan.platform == "tpu" and stats["attention_impl"] == "auto":
        raise RuntimeError("attention impl was never resolved")
    return dev


def engine_argv(plan: Plan, model: str, port: int, extra: str = "") -> List[str]:
    options = f"--model {model} --port {port} {plan.engine_options} {extra}"
    return [sys.executable, "-m", f"{PKG}.engine.server"] + options.split()


# -- phase 1: kernels ----------------------------------------------------------


def check_kernels(plan: Plan) -> List[Dict[str, Any]]:
    """Runs IN a jax process (the ``--child kernels`` child; in-process from
    the CPU rehearsal): each Pallas kernel through the serving dispatcher
    (ops/attention.py, impl="pallas") against the XLA reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_fast_model_actuation_tpu.ops import attention as attn

    dev = jax.devices()[0]
    if dev.platform != plan.platform:
        raise RuntimeError(
            f"kernels phase needs a {plan.platform} device, jax has {dev}"
        )
    ps, ctx = plan.page_size, plan.context
    pps = ctx // ps
    rows: List[Dict[str, Any]] = []

    def compare(name, shape, fn, args, valid=None):
        pallas = jax.jit(lambda *a: fn(*a, impl="pallas"))
        if not plan.interpret:
            if "tpu_custom_call" not in pallas.lower(*args).as_text():
                raise RuntimeError(f"{name}: no tpu_custom_call was lowered")
        t0 = time.monotonic()
        got = np.asarray(pallas(*args), np.float32)
        first_s = time.monotonic() - t0
        want = np.asarray(
            jax.jit(lambda *a: fn(*a, impl="reference"))(*args), np.float32
        )
        if valid is not None:
            got, want = got[valid], want[valid]
        err = float(np.max(np.abs(got - want)))
        if not np.isfinite(got).all() or err > KERNEL_ATOL:
            raise RuntimeError(
                f"{name} {shape}: max |pallas - reference| = {err} "
                f"(bound {KERNEL_ATOL})"
            )
        rows.append(
            {"kernel": name, "shape": list(shape), "max_abs_err": err,
             "compile_and_run_s": round(first_s, 3)}
        )

    for shape in plan.head_shapes:
        h, kvh, d = shape
        keys = iter(jax.random.split(jax.random.key(h * 1000 + d), 16))

        def rand(*dims):
            return jax.random.normal(next(keys), dims, jnp.float32).astype(
                jnp.bfloat16
            )

        batch = 8
        num_pages = batch * pps + 1
        # the stored layout (engine/kv_cache.py); the kernels read layer 1
        layer = 1
        k_pages = rand(2, num_pages, ps, kvh * d)
        v_pages = rand(2, num_pages, ps, kvh * d)
        table = jnp.asarray(
            1 + np.random.default_rng(0).permutation(batch * pps).reshape(
                batch, pps
            ),
            jnp.int32,
        )
        # ragged lengths: full context, one token, page edges, mid-page
        lens = jnp.asarray(
            [ctx, 1, ps, ps + 1, ctx - 1, ctx // 2 + 3, 3 * ps, ctx - ps][
                :batch
            ],
            jnp.int32,
        )
        compare(
            "decode", shape, attn.paged_decode_attention,
            (rand(batch, h, d), k_pages, v_pages, table, lens, layer),
        )
        compare(
            "decode_inline", shape, attn.paged_decode_attention_inline,
            (rand(batch, h, d), k_pages, v_pages, rand(batch, kvh, d),
             rand(batch, kvh, d), table, lens - 1, layer),
        )
        # one packed buffer: a decode row, a prefill segment from position 0
        # and a suffix continuation deep in the context, each on its own
        # RAGGED_BLOCK boundary, padding rows between
        qb = attn.RAGGED_BLOCK
        segs = [(0, ctx - 1, 1), (1, 0, min(37, ctx)), (2, ctx // 2, ctx // 4)]
        slot, pos = [], []
        for s, p0, n in segs:
            slot += [s] * n
            pos += list(range(p0, p0 + n))
            pad = -len(slot) % qb
            slot += [-1] * pad
            pos += [0] * pad
        slot_a, pos_a = np.asarray(slot, np.int32), np.asarray(pos, np.int32)
        compare(
            "ragged", shape, attn.ragged_paged_attention,
            (rand(len(slot), h, d), k_pages, v_pages, table,
             jnp.asarray(slot_a), jnp.asarray(pos_a), layer),
            valid=slot_a >= 0,
        )
        seq = ctx
        sl = jnp.asarray([seq, seq // 3], jnp.int32)
        compare(
            "prefill", shape, attn.causal_prefill_attention,
            (rand(2, seq, h, d), rand(2, seq, kvh, d), rand(2, seq, kvh, d),
             sl),
            valid=np.arange(seq)[None, :] < np.asarray(sl)[:, None],
        )
    return rows


def phase_kernels(plan: Plan) -> None:
    with Child(
        "kernels", [sys.executable, os.path.abspath(__file__), "--child-kernels"]
    ) as child:
        rc = child.proc.wait(timeout=plan.ready_timeout_s)
        text = child.log_text()
    for line in text.splitlines():
        if line.startswith('{"phase": "kernels"'):
            print(line, flush=True)
    if rc != 0:
        raise RuntimeError(f"kernels child failed rc={rc}:\n{text[-4000:]}")


# -- phase 2: the engine server ------------------------------------------------


def phase_server(plan: Plan) -> Dict[str, Any]:
    """A stand-alone engine server with default flags: completions, level-1
    sleep (no device release: this phase is the pinned_host offload), wake,
    the same tokens. Returns the device the engine reported."""
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    argv = engine_argv(plan, plan.model, port, "--sleep-release-devices never")
    with Child("server", argv) as child:
        ready_s = wait_healthy(base + "/health", child, plan.ready_timeout_s)
        short, long_ = (prompt_of(n) for n in plan.prompt_lens)
        t0 = time.monotonic()
        toks_short = complete(base, short, plan.max_tokens)
        first_request_s = time.monotonic() - t0
        toks_long = complete(base, long_, plan.max_tokens)
        streamed, stream_first_s = complete_streamed(
            base, short, plan.max_tokens
        )
        if streamed != toks_short:
            raise RuntimeError(
                f"streamed tokens {streamed} != unary tokens {toks_short}"
            )
        pair: List[Any] = [None, None]

        def one(i: int, prompt: List[int]) -> None:
            try:
                pair[i] = complete(base, prompt, plan.max_tokens)
            except Exception as e:  # noqa: BLE001 — re-raised below
                pair[i] = e

        threads = [
            threading.Thread(target=one, args=(i, p))
            for i, p in enumerate((short, long_))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        for r in pair:
            if not isinstance(r, list):
                raise RuntimeError(f"concurrent request failed: {r!r}")
        stats = http("GET", base + "/v1/stats")
        dev = check_stats(plan, stats, 1)
        if plan.platform == "tpu":
            if stats["attention_impl"] != "pallas":
                raise RuntimeError(
                    f"auto resolved to {stats['attention_impl']!r} on the "
                    "chip, not to the Pallas kernels"
                )
            if f"attention impl {stats['attention_impl']}" not in child.log_text():
                raise RuntimeError("start-up log does not name the impl")
        slept = http("POST", base + "/sleep?level=1")
        if not slept["is_sleeping"] or slept["devices_released"]:
            raise RuntimeError(f"unexpected sleep state: {slept}")
        if plan.platform == "tpu" and not slept["pinned_host"]:
            raise RuntimeError(
                f"level-1 sleep did not stage into pinned_host: {slept}"
            )
        woke = http("POST", base + "/wake_up")
        if woke["is_sleeping"]:
            raise RuntimeError(f"wake left the engine asleep: {woke}")
        again = complete(base, short, plan.max_tokens)
        if again != toks_short:
            raise RuntimeError(
                f"tokens after wake {again} != before sleep {toks_short}"
            )
        cache = http("GET", base + "/v1/stats")["compile_cache"]
    emit(
        "server", model=plan.model, ready_s=round(ready_s, 2),
        first_request_s=round(first_request_s, 2),
        stream_first_token_s=round(stream_first_s, 3),
        attention_impl=stats["attention_impl"],
        # reported, not required: the long prompt's second serving hits the
        # prefix cache (suffix program, XLA attention) where its first ran
        # the prefill kernel, and random weights have near-flat logits
        concurrent_equal_sequential=[
            pair[0] == toks_short, pair[1] == toks_long
        ],
        sleep_s=round(slept["last_sleep_seconds"], 3),
        wake_s=round(woke["last_wake_seconds"], 3),
        bytes_offloaded=slept["bytes_offloaded"],
        pinned_host=slept["pinned_host"], same_tokens_after_wake=True,
        compile_cache=cache, device=dev,
    )
    return dev


# -- phase 3: the launcher, two models time-sharing one chip -------------------


def phase_launcher(plan: Plan) -> None:
    subprocess.run(
        ["make", "-C", os.path.join(ROOT, "native")], check=True,
        stdout=subprocess.DEVNULL,
    )
    lport, port_a, port_b = free_port(), free_port(), free_port()
    launcher = f"http://127.0.0.1:{lport}"
    a, b = f"http://127.0.0.1:{port_a}", f"http://127.0.0.1:{port_b}"
    argv = [
        sys.executable, "-m", f"{PKG}.launcher.main", "--host", "127.0.0.1",
        "--port", str(lport), "--log-dir", LOG_DIR, *plan.launcher_args,
    ]
    prompt = prompt_of(plan.prompt_lens[0])
    engine_pids: List[int] = []

    def create(iid: str, model: str, port: int, chip: str) -> float:
        t0 = time.monotonic()
        options = f"--model {model} --port {port} {plan.engine_options}"
        out = http(
            "PUT", f"{launcher}/v2/vllm/instances/{iid}",
            {"options": options, "gpu_uuids": [chip]},
        )
        if out["status"] != "started":
            raise RuntimeError(f"create {iid}: {out}")
        engine_pids.append(out["pid"])

        def dead() -> Optional[str]:
            inst = f"{launcher}/v2/vllm/instances/{iid}"
            if http("GET", inst)["status"] == "running":
                return None
            with urllib.request.urlopen(inst + "/log", timeout=30) as resp:
                return f"instance {iid} died:\n" + resp.read().decode(
                    errors="replace"
                )[-4000:]

        wait_healthy(
            f"http://127.0.0.1:{port}/health", child, plan.ready_timeout_s,
            dead,
        )
        return time.monotonic() - t0

    # engine children lead their own process groups: on any exit, none may
    # outlive the launcher that stops them at shutdown
    with Child("launcher", argv, also_kill=engine_pids) as child:
        wait_healthy(launcher + "/health", child, plan.ready_timeout_s)
        chips = http("GET", launcher + "/")["chips"]
        if plan.platform == "tpu" and not chips["mode"].startswith("real:"):
            raise RuntimeError(f"launcher is not on real chips: {chips}")
        chip = chips["ids"][0]

        create_a_s = create("smoke-a", plan.model, port_a, chip)
        toks_a = complete(a, prompt, plan.max_tokens)
        stats_a = http("GET", a + "/v1/stats")
        check_stats(plan, stats_a, 1)

        # A sleeps with device release (the TPU default): the chip is free
        t0 = time.monotonic()
        slept = http("POST", a + "/sleep?level=1")
        release_s = time.monotonic() - t0
        if not slept["devices_released"]:
            raise RuntimeError(
                f"release-mode sleep did not free the chip in process: {slept}"
            )

        # B, another model, on the SAME chip: the ledger admits it only
        # because A verifiably released
        create_b_s = create("smoke-b", plan.second_model, port_b, chip)
        toks_b = complete(b, prompt, plan.max_tokens)
        stats_b = http("GET", b + "/v1/stats")
        check_stats(plan, stats_b, 1)
        out = http("DELETE", f"{launcher}/v2/vllm/instances/smoke-b")
        if out["status"] != "terminated":
            raise RuntimeError(f"delete smoke-b: {out}")

        # A takes the chip back and answers as before
        t0 = time.monotonic()
        woke = http("POST", a + "/wake_up")
        wake_call_s = time.monotonic() - t0
        again, first_s = complete_streamed(a, prompt, plan.max_tokens)
        wake_to_first_token_s = wake_call_s + first_s
        if again != toks_a:
            raise RuntimeError(
                f"tokens after reacquire {again} != before release {toks_a}"
            )
        cache_after = http("GET", a + "/v1/stats")["compile_cache"]
        wake_cache = {
            k: cache_after[k] - stats_a["compile_cache"][k]
            for k in ("requests", "hits", "writes")
        }
        if plan.platform == "tpu" and wake_cache["hits"] == 0:
            raise RuntimeError(
                f"no wake-path compile hit the persistent cache: {wake_cache}"
            )
        http("DELETE", f"{launcher}/v2/vllm/instances/smoke-a")
    emit(
        "launcher", release_in_process=True, chip_mode=chips["mode"],
        chip=chip,
        models=[plan.model, plan.second_model],
        create_a_s=round(create_a_s, 2), create_b_s=round(create_b_s, 2),
        release_s=round(release_s, 3),
        release_transfer_s=round(slept["last_sleep_seconds"], 3),
        reacquire_s=round(woke["last_reacquire_seconds"], 3),
        wake_s=round(woke["last_wake_seconds"], 3),
        wake_to_first_token_s=round(wake_to_first_token_s, 3),
        wake_compile_cache=wake_cache, same_tokens_after_reacquire=True,
        attention_impl=[stats_a["attention_impl"], stats_b["attention_impl"]],
        second_model_tokens=len(toks_b),
    )


# -- phase 4 (--chips 4): one model sharded over four chips --------------------


def phase_four_chips(plan: Plan) -> Dict[str, Any]:
    """The tp-sharded engine under the Pallas shard_map kernels against the
    GSPMD-partitioned XLA path on the same mesh (``compare_impls``)."""
    prompts = [prompt_of(n) for n in plan.prompt_lens]
    under_test: Optional[List[Dict[str, List[Any]]]] = None
    dev: Dict[str, Any] = {}
    for impl in plan.tp_impls:
        port = free_port()
        base = f"http://127.0.0.1:{port}"
        argv = engine_argv(
            plan, plan.tp_model, port,
            f"--tensor-parallel-size {plan.tp} --attention-impl {impl}",
        )
        with Child(f"tp{plan.tp}-{impl}", argv) as child:
            ready_s = wait_healthy(
                base + "/health", child, plan.ready_timeout_s
            )
            scored = [
                complete_scored(base, p, plan.max_tokens) for p in prompts
            ]
            stats = http("GET", base + "/v1/stats")
            compared = None
            if under_test is not None:  # this server is the reference
                compared = [
                    compare_impls(
                        a, b,
                        lambda i, p=p, a=a: score_token(
                            base, p + a["tokens"][:i], a["tokens"][i]
                        ),
                    )
                    for p, a, b in zip(prompts, under_test, scored)
                ]
                # the scoring path against this server's own decode path
                echo_diff = max(
                    abs(score_token(base, p, b["tokens"][0]) - b["logprobs"][0])
                    for p, b in zip(prompts, scored)
                )
        dev = check_stats(plan, stats, plan.tp)
        if stats["attention_impl"] != impl:
            raise RuntimeError(f"engine ran {stats['attention_impl']}, not {impl}")
        hbm = stats["hbm"]
        state = [row["state_bytes"] for row in hbm]
        # heads, kv heads, mlp and vocab axes are sharded over tp; only the
        # norm vectors are replicated
        if len(hbm) != plan.tp or max(state) > 1.02 * min(state):
            raise RuntimeError(f"state is not spread evenly over tp: {hbm}")
        in_use = [row["bytes_in_use"] for row in hbm]
        if None not in in_use and max(in_use) > 1.25 * min(in_use):
            raise RuntimeError(f"device memory in use is uneven: {hbm}")
        emit(
            "four_chips", impl=impl, model=plan.tp_model, tp=plan.tp,
            ready_s=round(ready_s, 2), state_bytes_per_chip=state,
            bytes_in_use_per_chip=in_use, compile_cache=stats["compile_cache"],
            device=dev,
        )
        if compared is None:
            under_test = scored
            continue
        emit(
            "four_chips", impls=list(plan.tp_impls), compared=compared,
            same_tokens=all(c["tokens_agreeing"] == c["of"] for c in compared),
            logprob_tol=IMPL_LOGPROB_TOL,
            echo_vs_decode_logprob_diff=round(echo_diff, 4),
        )
        if not all(c["ok"] for c in compared):
            raise RuntimeError(
                f"{plan.tp_impls[0]} disagrees with {impl} beyond a "
                f"near-tie: {compared}"
            )
    return dev


# -- entry ----------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the four-chip phase and what it is compared with",
    )
    p.add_argument("--child-kernels", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    plan = Plan()

    if args.child_kernels:
        for row in check_kernels(plan):
            emit("kernels", **row)
        return 0

    # one rule for the compile cache, exported to every child
    sys.path.insert(0, ROOT)
    from llm_d_fast_model_actuation_tpu.utils import compile_cache

    emit("start", chips=args.chips, compile_cache_dir=compile_cache.arm())
    t0 = time.monotonic()
    if args.chips == 4:
        dev = phase_four_chips(plan)
    else:
        phase_kernels(plan)
        dev = phase_server(plan)
        phase_launcher(plan)
    emit("done", seconds=round(time.monotonic() - t0, 1))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
