"""On-chip perf exploration for the serving engine (not the headline bench).

Sweeps the knobs that bound decode throughput on one v5e chip — decode
chunk length (dispatch amortization), batch size, attention impl (pallas vs
grouped), int8 — and measures the wake->TTFT path with the exact post-wake
program warmed, plus the raw host<->device bandwidth that bounds every
bulk-transfer number (checkpoint load, release snapshot).

Run:  python scripts/tpu_profile.py [--quick]
Prints one JSON object per experiment, then a SUMMARY json line.
"""

import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def main() -> None:
    import jax
    import numpy as np

    from llm_d_fast_model_actuation_tpu.utils import compile_cache

    compile_cache.arm()

    from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
    from llm_d_fast_model_actuation_tpu.engine.server import MODEL_CONFIGS
    from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
    from llm_d_fast_model_actuation_tpu.models import checkpoint, llama

    quick = "--quick" in sys.argv
    on_tpu = jax.devices()[0].platform == "tpu"
    results = {}

    def report(name, **kw):
        results[name] = kw
        print(json.dumps({"exp": name, **kw}), flush=True)

    # --- raw host<->device bandwidth -------------------------------------------
    from llm_d_fast_model_actuation_tpu.utils.bandwidth import (
        measure_host_device_bandwidth,
    )

    probe_mib = 256
    h2d, d2h = measure_host_device_bandwidth(probe_mib)
    report(
        "host_device_bandwidth",
        h2d_gibps=round(h2d, 3),
        d2h_gibps=round(d2h, 3),
        mib=probe_mib,
    )

    model_name = "bench-1b" if on_tpu else "tiny"
    if on_tpu:
        model = MODEL_CONFIGS[model_name]()
        prompt_len = 128
    else:
        model = llama.LlamaConfig.tiny()
        prompt_len = 16

    ckpt_dir = os.environ.get(
        "FMA_BENCH_CKPT", f"/tmp/fma-bench-ckpt-{model_name}"
    )
    if not os.path.isdir(os.path.join(ckpt_dir, checkpoint.PARAMS_DIR)):
        params = llama.init_params(jax.random.key(0), model)
        params = jax.block_until_ready(params)
        checkpoint.save_params(ckpt_dir, model, params)
        del params
    t0 = time.monotonic()
    params = checkpoint.load_params(ckpt_dir, model)
    params = jax.block_until_ready(params)
    report("ckpt_load", seconds=round(time.monotonic() - t0, 2))

    rng = np.random.default_rng(0)

    def measure_decode(engine, decode_steps):
        prompts = [
            rng.integers(1, model.vocab_size, prompt_len).tolist()
            for _ in range(engine.cfg.max_batch)
        ]
        reqs = []
        for p in prompts:
            engine.add_request(p, max_new_tokens=decode_steps)
        while engine._waiting:
            reqs.extend(engine.step())
        emitted_at_t0 = sum(
            len(r.out_tokens) for r in engine._slots if r is not None
        ) + sum(len(r.out_tokens) for r in reqs)
        t0 = time.monotonic()
        while engine.has_work():
            reqs.extend(engine.step())
        dt = time.monotonic() - t0
        emitted = sum(len(r.out_tokens) for r in reqs) - emitted_at_t0
        return emitted / dt if dt > 0 else 0.0

    import dataclasses

    def make_engine(batch, chunk, attn="auto", quant="", pipeline=False):
        m = model
        if quant:
            from llm_d_fast_model_actuation_tpu.models.registry import (
                maybe_quantize,
            )

            m = dataclasses.replace(model, quantization=quant)
            p = maybe_quantize(m, params)
        else:
            p = params
        if attn != "auto":
            m = dataclasses.replace(m, attention_impl=attn)
        # KV capacity must cover the full admitted batch at this
        # config's decode budget, or _admit defers requests and the
        # timed window measures a shrinking batch instead of steady state
        per_req = -(-(prompt_len + steps_for(chunk)) // 16)
        cfg = EngineConfig(
            model=m, max_batch=batch, page_size=16,
            num_pages=max(512, batch * per_req + 8), max_seq_len=1024,
            decode_chunk=chunk, pipeline_decode=pipeline,
        )
        return InferenceEngine(cfg, params=p, seed=0)

    # decode budget per request: enough chunks that several full
    # dispatches land INSIDE the timed window. The untimed admission
    # drain consumes the prefill token plus one chunk, so a budget of
    # N*chunk+1 leaves N-1 timed dispatches (2 in --quick, 3 otherwise).
    def steps_for(chunk):
        return (3 if quick else 4) * chunk + 1

    # --- decode sweep: chunk x batch -----------------------------------------
    sweep = [(8, 16), (8, 32), (8, 64), (16, 32), (16, 64), (32, 64)]
    if quick:
        sweep = [(8, 16), (8, 32)]
    for batch, chunk in sweep:
        try:
            eng = make_engine(batch, chunk)
            t0 = time.monotonic()
            warm = eng.generate(
                [rng.integers(1, model.vocab_size, prompt_len).tolist()],
                max_new_tokens=4,
            )[0]
            compile_s = time.monotonic() - t0
            toks = measure_decode(eng, steps_for(chunk))
            report(
                f"decode_b{batch}_c{chunk}",
                tok_s=round(toks, 1),
                compile_s=round(compile_s, 1),
            )
            del eng
        except Exception as e:  # noqa: BLE001
            report(f"decode_b{batch}_c{chunk}", error=str(e)[:200])

    # --- pipelined decode at representative configs ---------------------------
    for batch, chunk in ([(8, 32)] if quick else [(8, 16), (8, 32), (8, 64)]):
        try:
            eng = make_engine(batch, chunk, pipeline=True)
            eng.generate(
                [rng.integers(1, model.vocab_size, prompt_len).tolist()],
                max_new_tokens=4,
            )
            toks = measure_decode(eng, steps_for(chunk))
            report(f"decode_b{batch}_c{chunk}_pipelined", tok_s=round(toks, 1))
            del eng
        except Exception as e:  # noqa: BLE001
            report(f"decode_b{batch}_c{chunk}_pipelined", error=str(e)[:200])

    # --- attention impl shootout (prefill-heavy + decode) --------------------
    for attn in ("grouped", "pallas"):
        try:
            eng = make_engine(8, 32, attn=attn)
            long_prompt = rng.integers(1, model.vocab_size, 512).tolist()
            eng.generate([long_prompt[:prompt_len]], max_new_tokens=2)
            t0 = time.monotonic()
            out = eng.generate([long_prompt], max_new_tokens=2)[0]
            prefill_s = time.monotonic() - t0
            toks = measure_decode(eng, steps_for(32))
            report(
                f"attn_{attn}",
                decode_tok_s=round(toks, 1),
                prefill512_s=round(prefill_s, 3),
                first_tok=int(out[0]),
            )
            del eng
        except Exception as e:  # noqa: BLE001
            report(f"attn_{attn}", error=str(e)[:300])

    # --- int8 at the best dense config ---------------------------------------
    try:
        eng = make_engine(8, 32, quant="int8")
        eng.generate(
            [rng.integers(1, model.vocab_size, prompt_len).tolist()],
            max_new_tokens=4,
        )
        toks = measure_decode(eng, steps_for(32))
        report("decode_int8_b8_c32", tok_s=round(toks, 1))
        del eng
    except Exception as e:  # noqa: BLE001
        report("decode_int8_b8_c32", error=str(e)[:300])

    # --- wake -> TTFT with the exact program set warmed ----------------------
    try:
        eng = make_engine(8, 16)
        prompt = rng.integers(1, model.vocab_size, prompt_len).tolist()
        warm = eng.generate([prompt], max_new_tokens=4)[0]
        warm1 = eng.generate([prompt], max_new_tokens=1)[0]
        mgr = attach_sleep(eng)
        mgr.sleep(1)
        t0 = time.monotonic()
        mgr.wake_up()
        wake_s = time.monotonic() - t0
        t0 = time.monotonic()
        first = eng.generate([prompt], max_new_tokens=1)[0]
        ttft = time.monotonic() - t0
        # and a second cycle (everything hot)
        mgr.sleep(1)
        t0 = time.monotonic()
        mgr.wake_up()
        wake2_s = time.monotonic() - t0
        t0 = time.monotonic()
        eng.generate([prompt], max_new_tokens=1)
        ttft2 = time.monotonic() - t0
        assert first[0] == warm1[0]
        report(
            "wake_ttft_warmed",
            wake_s=round(wake_s, 3),
            ttft_after_wake_s=round(ttft, 3),
            wake2_s=round(wake2_s, 3),
            ttft2_s=round(ttft2, 3),
        )
    except Exception as e:  # noqa: BLE001
        report("wake_ttft_warmed", error=str(e)[:300])

    print("SUMMARY " + json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
