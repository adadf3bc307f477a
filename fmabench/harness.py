"""One run of one cell: set-up, warm-up, the measured window, the readers,
the comparison with the reference, and the result line.

The parent never initialises JAX. The engine runs in a child
(``fmabench.serve``), the reference in another once the first has gone:
one process uses the chip at a time.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import shutil
import signal
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from . import client, readers, spec, traffic, xplane

PROFILE_SECONDS = 3.0
PROM_PERIOD_S = 0.5
WARMUP_TIMEOUT_S = 300.0
ALONE_TOKENS = 40


def log(event: str, /, **fields: Any) -> None:
    """An earlier line: anything but the result goes here."""
    print(json.dumps({"fmabench": event, **fields}), flush=True)


class Window:
    """What a driver hands back: the records that ended inside the window
    and whatever else the metrics read."""

    def __init__(self) -> None:
        self.t_open = 0.0
        self.t_close = 0.0
        self.wall_open = 0.0
        self.wall_close = 0.0
        self.records: List[client.Record] = []
        self.meter = client.TokenMeter()
        self.e2e: Dict[str, float] = {}
        self.series: Dict[str, List[float]] = {}
        self.notes: Dict[str, Any] = {}
        #: (time, live sequences, their context tokens) through the profile
        self.live_samples: List[Tuple[float, int, int]] = []


class Hooks:
    """What the harness does around a driver's window (counters, profile)."""

    def __init__(self, base: str, traced: bool, profile_dir: str, seconds: float):
        self.base = base
        self.traced = traced
        self.profile_dir = profile_dir
        self.seconds = seconds
        self.ev = readers.Evidence()
        self.profile_at: Optional[Tuple[float, float]] = None
        self.t_open_mono = 0.0
        self._bg: List[asyncio.Task] = []

    async def open(self, win: Window, live: Any = None) -> None:
        """``live`` samples (sequences decoding, their context tokens)
        through the profiled stretch, which lies in mid-window."""
        self.ev.stats_open = await client.in_thread(
            client.http, "GET", self.base + "/v1/stats"
        )
        win.t_open, win.wall_open = time.monotonic(), time.time()
        self.t_open_mono = win.t_open
        win.t_close = win.t_open + self.seconds
        win.meter.open, win.meter.close = win.t_open, win.t_close
        if self.traced:
            self._bg.append(asyncio.ensure_future(self._poll_prom(win)))
            self._bg.append(asyncio.ensure_future(self._profile(win, live)))

    async def close(self, win: Window) -> None:
        win.wall_close = time.time()
        for t in self._bg:
            try:
                await asyncio.wait_for(t, timeout=120)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                t.cancel()
        self.ev.stats_close = await client.in_thread(
            client.http, "GET", self.base + "/v1/stats"
        )
        if self.traced:
            doc = await client.in_thread(
                client.http, "GET", self.base + "/v1/traces"
            )
            self.ev.spans = (doc or {}).get("traceEvents", [])
        self.ev.wall_open, self.ev.wall_close = win.wall_open, win.wall_close
        self.ev.series = win.series

    async def _poll_prom(self, win: Window) -> None:
        import re

        pat = re.compile(r"^(fma_[a-z_]+)\{[^}]*\} ([0-9.eE+-]+)$", re.M)
        while time.monotonic() < win.t_close:
            try:
                text = await client.in_thread(
                    client.http_text, self.base + "/metrics"
                )
                for name, val in pat.findall(text):
                    self.ev.prom.setdefault(name, []).append(float(val))
            except OSError:
                pass
            await asyncio.sleep(PROM_PERIOD_S)

    async def _profile(self, win: Window, live: Any) -> None:
        """The program's profiler, on for a few seconds."""
        start = win.t_open + max(0.0, (self.seconds - PROFILE_SECONDS) / 2)
        await asyncio.sleep(max(0.0, start - time.monotonic()))
        shutil.rmtree(self.profile_dir, ignore_errors=True)
        t0 = time.monotonic()
        await client.in_thread(
            client.http, "POST", self.base + "/v1/profile",
            {"log_dir": self.profile_dir},
        )
        t_on = time.monotonic()
        while time.monotonic() < t_on + min(PROFILE_SECONDS, self.seconds):
            if live is not None:
                win.live_samples.append((time.monotonic(), *live()))
            await asyncio.sleep(0.1)
        t_off = time.monotonic()
        await client.in_thread(client.http, "DELETE", self.base + "/v1/profile")
        self.profile_at = (t_on, t_off)
        log("profile", start_call_s=round(t_on - t0, 3),
            traced_s=round(t_off - t_on, 3),
            stop_call_s=round(time.monotonic() - t_off, 3))


# -- the two drivers ------------------------------------------------------------


async def warmup_ladder(base: str, mix: Dict[str, Any], vocab: int, seed: int) -> None:
    """Every prompt-length shape the mix can meet, once (the engine prefills
    them one after another whatever the client does), and then one request
    served alone."""
    lens = mix["warmup"]["prompt_lens"]
    prompts = traffic.Prompts(vocab, seed, "ladder")
    reqs = [{"prompt": prompts.draw(n), "max_tokens": 2} for n in lens]
    t0 = time.monotonic()
    recs = await client.run_together(base, reqs, "ladder")
    # one request served alone through several decode chunks: a chunk that
    # follows a chunk with no admission between takes the engine's
    # device-resident scheduler state, which is a program of its own
    alone = {"prompt": prompts.draw(min(lens)), "max_tokens": ALONE_TOKENS}
    recs += await client.run_together(base, [alone], "ladder")
    bad = [r.error for r in recs if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up ladder failed: {bad[:3]}")
    log("warmup_ladder", requests=len(recs), seconds=round(time.monotonic() - t0, 2))


def _live(records) -> Tuple[int, int]:
    """(sequences decoding now, the sum of their context lengths)."""
    rows = [r for r in records if r.first and not r.error
            and len(r.tokens) < r.max_tokens]
    return len(rows), sum(len(r.prompt) + len(r.tokens) for r in rows)


def _reduce(
    win: Window, done: List[client.Record], inflight: Any, seconds: float
):
    """The window's records and its end-to-end numbers; returns the
    requests that completed. A request that was due when the window opened
    and is still unfinished at its close had the whole window and did not
    end: it counts as failed. The others in flight at the close are not
    counted at all (``unfinished_at_close``)."""
    win.records = [
        r for r in done if r.ended and win.t_open <= r.ended < win.t_close
    ]
    unfinished = [r for r in inflight if r.ended is None]
    overdue = [r for r in unfinished if r.due <= win.t_open]
    win.records += overdue
    win.notes.update(unfinished_at_close=len(unfinished),
                     overdue_at_close=len(overdue),
                     out_tokens_by_tenth=win.meter.by_tenth)
    ok = [r for r in win.records if r.ok]
    win.e2e["out_tokens_per_s"] = win.meter.count / seconds
    _latencies(win, ok, seconds)
    return ok


def _latencies(win: Window, ok: List[client.Record], seconds: float) -> None:
    """Token-gap and first-token series over ALL requests that ended in the
    window; a request that failed counts as the worst latency, the whole
    window."""
    failed = len(win.records) - len(ok)
    tp = [r.tpot_s() for r in ok if r.tpot_s() is not None]
    tp += [seconds] * failed
    if tp:
        win.e2e["tpot_p95_ms"] = 1e3 * traffic.percentile(tp, 95)
        win.series["tpot_ms"] = [1e3 * x for x in tp]
    win.series["ttft_ms"] = [1e3 * (r.first - r.due) for r in ok]
    win.series["ttft_ms"] += [1e3 * seconds] * failed


async def drive_closed(
    base: str, mix: Dict[str, Any], vocab: int, seed: int, hooks: Hooks
) -> Window:
    """N clients, each sending its next request when its last completes."""
    win = Window()
    n = int(mix["clients"])
    stream = traffic.requests_stream(mix, vocab, seed, "window")
    done: List[client.Record] = []
    inflight: Dict[int, client.Record] = {}

    async def one_client(i: int, session: Any) -> None:
        first = True
        while True:
            req = next(stream)
            if first:
                # steady state at once: the first wave is as far through
                # its output as a running system's requests would be
                req = {**req, "max_tokens": max(
                    2, round(req["max_tokens"] * (i + 0.5) / n))}
                first = False
            rec = client.Record(req, time.monotonic())
            inflight[i] = rec
            await client.stream_one(session, base, rec, win.meter)
            done.append(rec)

    async with client.new_session(n + 4) as session:
        tasks = [asyncio.ensure_future(one_client(i, session)) for i in range(n)]
        try:
            t0 = time.monotonic()
            # the first wave is graded in length, so after n // 2 completions
            # the slots hold requests at every stage of their output
            while len(done) < n // 2:
                await asyncio.sleep(0.05)
                if time.monotonic() - t0 > WARMUP_TIMEOUT_S:
                    raise RuntimeError("warm-up traffic never reached steady state")
                for t in tasks:
                    if t.done():
                        t.result()
            log("warmup_traffic", completed=len(done),
                seconds=round(time.monotonic() - t0, 2))
            await hooks.open(win, lambda: _live(inflight.values()))
            await asyncio.sleep(max(0.0, win.t_close - time.monotonic()))
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
    ok = _reduce(win, done, inflight.values(), hooks.seconds)
    win.notes.update(out_tokens=win.meter.count, completed=len(ok))
    await hooks.close(win)
    return win


async def drive_open(
    base: str, mix: Dict[str, Any], vocab: int, seed: int, hooks: Hooks
) -> Window:
    """Arrivals on a schedule fixed beforehand, at the rate in the file."""
    win = Window()
    warm_s = float(mix["warmup"]["seconds"])
    sched_w, sched = traffic.open_schedule(
        mix, vocab, seed, hooks.seconds, warm_s
    )
    done: List[client.Record] = []
    pending: List[asyncio.Task] = []
    inflight: List[client.Record] = []

    async def fire(session: Any, rec: client.Record) -> None:
        inflight.append(rec)
        await client.stream_one(session, base, rec, win.meter)
        inflight.remove(rec)
        done.append(rec)

    async def dispatch(session: Any, t0: float, rows, tag: str) -> None:
        for row in rows:
            due = t0 + row["t_s"]
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = client.Record(row, due, tag)
            pending.append(asyncio.ensure_future(fire(session, rec)))

    async with client.new_session(1024) as session:
        try:
            t0 = time.monotonic()
            await dispatch(session, t0, sched_w, "warmup")
            await asyncio.sleep(max(0.0, t0 + warm_s - time.monotonic()))
            log("warmup_traffic", sent=len(sched_w), completed=len(done),
                seconds=round(time.monotonic() - t0, 2))
            await hooks.open(win, lambda: _live(inflight))
            await dispatch(session, win.t_open, sched, "sched")
            await asyncio.sleep(max(0.0, win.t_close - time.monotonic()))
        finally:
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
    ok = _reduce(win, done, inflight, hooks.seconds)
    sent = [r for r in done + inflight if r.tag == "sched"]
    late = [r.sent - r.due for r in sent]
    win.notes.update(
        scheduled=len(sched), sent=len(sent), completed=len(ok),
        generator_late_ms_max=round(1e3 * max(late, default=0.0), 3),
        generator_late_ms_mean=round(1e3 * sum(late) / max(1, len(late)), 3),
        out_tokens=win.meter.count,
    )
    await hooks.close(win)
    return win


DRIVERS = {"closed": drive_closed, "open": drive_open}


# -- the comparison with the reference ------------------------------------------


def sample_for_check(
    records: List[client.Record], seed: int, want: int
) -> List[client.Record]:
    """A seeded sample of the finished requests, the longest in it."""
    ok = [r for r in records if r.ok]
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in ok if r is not longest]
    random.Random(f"{seed}:check").shuffle(rest)
    return [longest] + rest[: max(0, want - 1)]


def reference_job(
    cell: spec.Cell, seed: int, sample: List[client.Record], platform: str
) -> Dict[str, Any]:
    """What the reference child is told: the family whose ``reference.py``
    it loads (and where a rehearsal keeps its own), that family's sizes,
    and the prompts with their served tokens."""
    return {
        "family": cell.family.name, "data_dir": cell.data_dir,
        "dims": cell.dims, "seed": seed, "platform": platform,
        "requests": [{"prompt": r.prompt, "tokens": r.tokens} for r in sample],
    }


def run_reference(
    cell: spec.Cell, seed: int, sample: List[client.Record], platform: str,
    out_dir: str, control: str = "",
) -> Dict[str, Any]:
    job = reference_job(cell, seed, sample, platform)
    tagged = "control" if control else "reference"
    job_path = os.path.join(out_dir, f"{tagged}_job.json")
    res_path = os.path.join(out_dir, f"{tagged}_result.json")
    with open(job_path, "w", encoding="utf-8") as f:
        json.dump(job, f)
    if os.path.exists(res_path):
        os.remove(res_path)
    argv = [sys.executable, "-m", "fmabench.reference", "--job", job_path,
            "--out", res_path]
    if control:
        argv += ["--control", control]
    env = {"JAX_PLATFORMS": "cpu"} if platform == "cpu" else {}
    with client.Child(tagged, argv, out_dir, env) as child:
        rc = child.proc.wait(timeout=900)
        if rc != 0:
            raise RuntimeError(f"{tagged} child failed rc={rc}:\n{child.log_tail()}")
    return spec.load_json(res_path)


#: the numbers a configuration may have compared, each with a limit of its
#: own (PERF.md section 2), over every served token of the sampled requests:
#: the widest gap, the mean gap and the gap's 95th percentile. A
#: configuration compares those of them that separate the program from its
#: low-precision control, and names them by giving their limits.
GAP_NUMBERS = ("gap_max", "gap_mean", "gap_p95")


def _readings(ref: Dict[str, Any]) -> Dict[str, Any]:
    keys = ("compared_tokens", "seconds", "nonzero_share", "big_share", "quantiles")
    return {k: ref.get(k) for k in keys + GAP_NUMBERS}


def limits_for(cell: spec.Cell) -> Dict[str, float]:
    return dict((cell.config.get("check") or {}).get("limits") or {})


def decide(
    ref: Dict[str, Any], limits: Dict[str, float], n_sampled: int
) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """Each number compared beside its limit; correct when all hold."""
    checks: Dict[str, Dict[str, Any]] = {
        "requests_compared": {"value": n_sampled, "limit": 1, "holds": n_sampled >= 1},
        "logits_finite": {"value": int(bool(ref.get("finite"))), "limit": 1,
                          "holds": bool(ref.get("finite"))},
    }
    compared = [name for name in GAP_NUMBERS if name in limits]
    if not compared or set(limits) - set(GAP_NUMBERS):
        raise KeyError(
            f"check.limits has to name some of {GAP_NUMBERS} and nothing else; "
            f"it names {sorted(limits)}"
        )
    for name in compared:
        v = ref.get(name)
        checks[name] = {"value": v, "limit": limits[name],
                        "holds": v is not None and v <= limits[name]}
    return all(c["holds"] for c in checks.values()), checks


# -- one run -------------------------------------------------------------------------


def run_cell(args: Any, t_start: float) -> int:
    bench = spec.benchmark(args.benchmark)
    cell = spec.Cell(bench, args.workload)
    traced = bool(args.trace)
    platform = "cpu" if args.rehearse else "tpu"
    if args.rehearse and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        print("--rehearse needs JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    out_dir = os.path.join(spec.OUT_DIR, cell.name)
    os.makedirs(out_dir, exist_ok=True)
    config_path = os.path.join(out_dir, "config_as_run.json")
    with open(config_path, "w", encoding="utf-8") as f:
        json.dump(cell.config, f)
    memory_file = os.path.join(out_dir, "memory.json")
    profile_dir = os.path.join(out_dir, "profile")
    if os.path.exists(memory_file):
        os.remove(memory_file)
    dims = cell.dims
    kind = cell.traffic["kind"]
    port = client.free_port()
    base = f"http://127.0.0.1:{port}"
    argv = client.server_argv(
        cell, config_path, port, args.seed, traced, memory_file, platform,
        args.serve_module,
    )
    hooks = Hooks(base, traced, profile_dir, float(args.seconds))
    hooks.ev.engine_option = cell.engine_option
    hooks.ev.data_dir = cell.data_dir
    with client.Child("server", argv, out_dir) as child:
        ready_s = client.wait_healthy(base + "/health", child, 1100)
        stats = client.http("GET", base + "/v1/stats")
        dev = stats["device"]
        if dev["platform"] != platform or dev["count"] < cell.chips:
            print(f"this cell needs {cell.chips} {platform} chip(s); the engine "
                  f"reports {dev}", file=sys.stderr)
            return 3
        log("server_ready", seconds=round(ready_s, 2), device=dev,
            attention_impl=stats.get("attention_impl"),
            compile_cache=stats.get("compile_cache"))

        async def drive() -> Window:
            await warmup_ladder(base, cell.traffic, dims["vocab_size"], args.seed)
            return await DRIVERS[kind](
                base, cell.traffic, dims["vocab_size"], args.seed, hooks
            )

        # set-up ends where the driver opens its window
        win = asyncio.run(drive())
        setup_s = win.t_open - t_start
        os.kill(child.proc.pid, signal.SIGUSR1)
        deadline = time.monotonic() + 10
        while not os.path.exists(memory_file) and time.monotonic() < deadline:
            time.sleep(0.05)
        memory = spec.load_json(memory_file) if os.path.exists(memory_file) else []
    # the server has gone and the chip is free
    ev = hooks.ev
    cc0 = (ev.stats_open.get("compile_cache") or {})
    cc1 = (ev.stats_close.get("compile_cache") or {})
    log("window", kind=kind, seconds=round(win.t_close - win.t_open, 3),
        setup_s=round(setup_s, 3), **win.notes,
        compiles_in_window=(cc1.get("requests", 0) - cc0.get("requests", 0)),
        compile_cache_open=cc0)
    failed = [r for r in win.records if not r.ok]
    attempted = len(win.records)
    peak = max((row.get("peak_bytes_in_use") or 0 for row in memory), default=0)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not traced:
        values = {**win.e2e, "setup_s": setup_s}
        for m in cell.end_to_end():
            if m["name"] not in values:
                raise RuntimeError(f"the {kind} driver gave no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        path = xplane.find_xplane(profile_dir)
        ev.trace = xplane.summarize(path) if path else None
        if ev.trace is not None and platform == "tpu":
            ev.peaks = spec.peaks(dev["kind"])
        if win.live_samples:
            ev.shapes = {
                **dims,
                "live_seqs": sum(s[1] for s in win.live_samples) / len(win.live_samples),
                "live_kv_tokens": sum(s[2] for s in win.live_samples) / len(win.live_samples),
            }
            log("live_context", live_seqs=round(ev.shapes["live_seqs"], 2),
                live_kv_tokens=round(ev.shapes["live_kv_tokens"], 1))
        for m in cell.per_layer():
            v = readers.read_metric(m["reader"], ev)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if ev.trace is not None:
            device["busy_s"] = ev.trace.busy_s
            device["window_s"] = ev.trace.window_s
            spans = _spans_on_trace_clock(ev, hooks)
            breakdown = {
                "device_ops": ev.trace.top_ops(10),
                "idle_gaps": ev.trace.idle_gaps(10, spans),
            }
            shutil.rmtree(profile_dir, ignore_errors=True)
    # the comparison: after the window, after the peak was read, chip free
    want = int((cell.traffic.get("check") or {}).get("sample", 6))
    sample = sample_for_check(win.records, args.seed, want)
    if sample:
        ref = run_reference(cell, args.seed, sample, platform, out_dir)
    else:
        ref = {"finite": False}
    correct, checks = decide(ref, limits_for(cell), len(sample))
    if args.control and sample:
        # by hand, when limits are set: the control's readings on the same
        # prompts and served tokens, on an earlier line; never part of a
        # run the driver makes
        ctl = run_reference(cell, args.seed, sample, platform, out_dir, args.control)
        log("control", control=args.control, **_readings(ctl),
            would_be_correct=decide(ctl, limits_for(cell), len(sample))[0])
    log("reference", **_readings(ref), per_request=ref.get("per_request"))
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": attempted, "failed": len(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {
        k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()
    }
    for k, c in checks.items():
        print(f"check {k}: value {c['value']} limit {c['limit']} "
              f"{'holds' if c['holds'] else 'FAILS'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _spans_on_trace_clock(ev: readers.Evidence, hooks: Hooks):
    """Program spans (wall clock, microseconds) moved onto the trace's
    clock. The trace counts from the profiler's start; the client knows
    when its start call returned, so the alignment is good to that call's
    length, which the ``profile`` line prints."""
    if not hooks.profile_at or ev.trace is None:
        return []
    t_on_wall = ev.wall_open + (hooks.profile_at[0] - hooks.t_open_mono)
    out = []
    for e in ev.spans:
        if e.get("ph") != "X":
            continue
        s = e["ts"] / 1e6 - t_on_wall
        out.append((e["name"], s, s + e["dur"] / 1e6))
    return out
