"""HF checkpoint import: numeric parity with the `transformers` forward.

A reference user brings vLLM-style HF model directories; `models/hf.py`
maps them onto our stacked param tree. These tests build tiny HF models,
save them, import them, and pin logits parity (fp32) and greedy-generation
parity against transformers itself — the strongest possible check that the
mapping (transposes, stacking, RoPE layout, biases, gemma conventions) is
exactly right.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import transformers
import torch

from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool
from llm_d_fast_model_actuation_tpu.models import hf, llama

TINY = dict(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=128,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
)


def _save(tmp_path, hf_cfg_cls, model_cls, **kw):
    cfg = hf_cfg_cls(**{**TINY, **kw})
    torch.manual_seed(0)
    m = model_cls(cfg)
    m.eval()
    d = str(tmp_path / "model")
    m.save_pretrained(d)
    return d, m


def _our_logits(cfg, params, tokens_np):
    b, s = tokens_np.shape
    num_pages, page_size = 16, 8
    shape = PagePool.pool_shape(
        cfg.num_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim
    )
    cache = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    pages_per_seq = -(-s // page_size)
    table = jnp.asarray(
        [
            [1 + i * pages_per_seq + j for j in range(pages_per_seq)]
            for i in range(b)
        ],
        dtype=jnp.int32,
    )
    seq_lens = jnp.full((b,), s, dtype=jnp.int32)
    logits, _ = llama.prefill(
        params, cfg, jnp.asarray(tokens_np, dtype=jnp.int32), seq_lens,
        cache, table,
    )
    return np.asarray(logits)


def _parity(tmp_path, hf_cfg_cls, model_cls, **kw):
    d, m = _save(tmp_path, hf_cfg_cls, model_cls, **kw)
    cfg, params = hf.load_model(d, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, TINY["vocab_size"], (2, 12))
    with torch.no_grad():
        ref = m(torch.from_numpy(tokens)).logits.float().numpy()
    ours = _our_logits(cfg, params, tokens)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)
    return d, m, cfg, params


def test_llama_logits_parity(tmp_path):
    _parity(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )


def test_llama_tied_embeddings_parity(tmp_path):
    d, m, cfg, _ = _parity(
        tmp_path,
        transformers.LlamaConfig,
        transformers.LlamaForCausalLM,
        tie_word_embeddings=True,
    )
    assert cfg.tie_embeddings


def test_qwen2_bias_parity(tmp_path):
    cfg = transformers.Qwen2Config(**TINY)
    torch.manual_seed(0)
    m = transformers.Qwen2ForCausalLM(cfg)
    # Qwen2 inits projection biases to zero; randomize them so this test
    # actually exercises the bias path, not just its shapes
    with torch.no_grad():
        for layer in m.model.layers:
            for proj in ("q_proj", "k_proj", "v_proj"):
                getattr(layer.self_attn, proj).bias.normal_(0.0, 0.1)
    m.eval()
    d = str(tmp_path / "model")
    m.save_pretrained(d)

    our_cfg, params = hf.load_model(d, dtype=jnp.float32)
    assert our_cfg.attn_bias
    assert float(jnp.abs(params["layers"]["bq"]).sum()) > 0
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, TINY["vocab_size"], (2, 12))
    with torch.no_grad():
        ref = m(torch.from_numpy(tokens)).logits.float().numpy()
    ours = _our_logits(our_cfg, params, tokens)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_gemma_parity(tmp_path):
    _parity(
        tmp_path,
        transformers.GemmaConfig,
        transformers.GemmaForCausalLM,
        head_dim=16,
        hidden_act="gelu_pytorch_tanh",
    )


def test_greedy_generation_matches_transformers(tmp_path):
    d, m = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    cfg, params = hf.load_model(d, dtype=jnp.float32)
    from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine

    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    eng = InferenceEngine(
        EngineConfig(
            model=cfg, max_batch=2, page_size=8, num_pages=32, max_seq_len=64,
            eos_token_id=-1,  # tiny random model: compare fixed-length output
        ),
        params=params,
    )
    ours = eng.generate([prompt], max_new_tokens=8)[0]
    with torch.no_grad():
        ref = m.generate(
            torch.tensor([prompt]),
            max_new_tokens=8,
            do_sample=False,
            eos_token_id=None,
            pad_token_id=0,
        )[0, len(prompt):].tolist()
    assert ours == ref


def test_rejects_unknown_architecture_and_missing_tensors(tmp_path):
    d, _ = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    import json, os

    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c["architectures"] = ["FalconForCausalLM"]
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    with pytest.raises(ValueError, match="unsupported architecture"):
        hf.config_from_hf(d)

    # restore arch, delete the weights: the loader names what's missing
    c["architectures"] = ["LlamaForCausalLM"]
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    for fn in os.listdir(d):
        if fn.endswith(".safetensors"):
            os.remove(os.path.join(d, fn))
    with pytest.raises(FileNotFoundError):
        hf.load_params(d, hf.config_from_hf(d))


def test_eos_token_id_list_takes_first(tmp_path):
    d, _ = _save(
        tmp_path,
        transformers.LlamaConfig,
        transformers.LlamaForCausalLM,
        eos_token_id=[7, 9],
    )
    assert hf.eos_token_id_from_hf(d) == 7


def test_engine_service_serves_hf_model(tmp_path):
    """End-to-end: `--model hf:<dir>` loads config + weights, serves, and a
    level-2 sleep/wake reloads the same weights from the HF directory."""
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    d, m = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    args = parse_engine_options(
        f"--model hf:{d} --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64"
    )
    svc = EngineService(args)
    try:
        # eos came from the HF config (transformers default = 2)
        assert svc.engine.cfg.eos_token_id == 2
        prompt = [3, 1, 4, 1, 5]
        fut = svc.submit(prompt, max_tokens=6, temperature=0.0)
        before = fut.result(timeout=60).out_tokens
        assert before

        svc.sleep(2)  # L2: weights discarded
        svc.wake_up()  # reload from the HF dir
        fut = svc.submit(prompt, max_tokens=6, temperature=0.0)
        after = fut.result(timeout=60).out_tokens
        assert after == before
    finally:
        svc.shutdown()


def test_parse_rejects_empty_hf_path():
    from llm_d_fast_model_actuation_tpu.engine.server import (
        parse_engine_options,
    )

    with pytest.raises(ValueError, match="hf:"):
        parse_engine_options("--model hf:")


def test_llama31_rope_scaling_parity(tmp_path):
    """Llama-3.1-style rope_scaling (banded NTK) must match transformers —
    silently dropping it would serve garbled long-context logits."""
    _parity(
        tmp_path,
        transformers.LlamaConfig,
        transformers.LlamaForCausalLM,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 32,
        },
        max_position_embeddings=128,
    )


def test_unsupported_rope_scaling_rejected(tmp_path):
    d, _ = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    import json, os

    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c["rope_scaling"] = {"rope_type": "yarn", "factor": 4.0}
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    with pytest.raises(ValueError, match="rope_scaling"):
        hf.config_from_hf(d)


def test_mistral_sliding_window_caps_context(tmp_path):
    d, _ = _save(
        tmp_path,
        transformers.MistralConfig,
        transformers.MistralForCausalLM,
        sliding_window=64,
    )
    cfg = hf.config_from_hf(d)
    # full attention within the window is exact; beyond it would silently
    # diverge from sliding-window semantics, so the context is capped
    assert cfg.max_seq_len == 64


def test_eos_from_generation_config(tmp_path):
    d, _ = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    import json, os

    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c.pop("eos_token_id", None)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    with open(os.path.join(d, "generation_config.json"), "w") as f:
        json.dump({"eos_token_id": [11, 13]}, f)
    assert hf.eos_token_id_from_hf(d, default=-1) == 11


def test_qwen3_qk_norm_parity(tmp_path):
    """Qwen3: per-head RMSNorm on q/k before RoPE, no projection biases."""
    d, m = _save(
        tmp_path,
        transformers.Qwen3Config,
        transformers.Qwen3ForCausalLM,
        head_dim=16,
    )
    cfg, params = hf.load_model(d, dtype=jnp.float32)
    assert cfg.qk_norm and not cfg.attn_bias
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, TINY["vocab_size"], (2, 12))
    with torch.no_grad():
        ref = m(torch.from_numpy(tokens)).logits.float().numpy()
    ours = _our_logits(cfg, params, tokens)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_mixtral_moe_parity(tmp_path):
    """Mixtral: routed MoE — router + per-expert SwiGLU stacks must match
    transformers' block-sparse forward."""
    d, m = _save(
        tmp_path,
        transformers.MixtralConfig,
        transformers.MixtralForCausalLM,
        num_local_experts=4,
        num_experts_per_tok=2,
    )
    from llm_d_fast_model_actuation_tpu.models.moe import MoeConfig

    cfg, params = hf.load_model(d, dtype=jnp.float32)
    assert isinstance(cfg, MoeConfig)
    assert cfg.num_experts == 4 and cfg.experts_per_token == 2
    assert params["layers"]["w_gate"].shape[:2] == (TINY["num_hidden_layers"], 4)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, TINY["vocab_size"], (2, 12))
    with torch.no_grad():
        ref = m(torch.from_numpy(tokens)).logits.float().numpy()
    ours = _our_logits(cfg, params, tokens)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_unrecognized_checkpoint_tensor_rejected(tmp_path):
    """A weight tensor with no place in the model must fail loudly, not be
    silently dropped (silently-dropped weights serve wrong logits)."""
    d, _ = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    import safetensors.torch as st
    import os

    fn = next(f for f in os.listdir(d) if f.endswith(".safetensors"))
    sd = st.load_file(os.path.join(d, fn))
    sd["model.layers.0.self_attn.q_proj.bias"] = torch.zeros(
        TINY["num_attention_heads"] * (TINY["hidden_size"] // TINY["num_attention_heads"])
    )
    st.save_file(sd, os.path.join(d, fn))
    with pytest.raises(ValueError, match="no place in the model config"):
        hf.load_params(d, hf.config_from_hf(d))


def test_prompt_logprobs_match_transformers(tmp_path):
    """echo+logprobs prompt scores must equal the model's actual
    next-token logprobs — checked against transformers, through BOTH the
    single-shot prefill and the chunked (segmented) prefill path."""
    d, m = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    cfg, params = hf.load_model(d, dtype=jnp.float32)
    from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine

    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    with torch.no_grad():
        logits = m(torch.tensor([prompt])).logits[0].float()
    norm = torch.log_softmax(logits, dim=-1)
    ref = [None] + [
        float(norm[i, prompt[i + 1]]) for i in range(len(prompt) - 1)
    ]

    for max_prefill in (0, 4):  # whole-prompt and 3-segment chunked
        eng = InferenceEngine(
            EngineConfig(
                model=cfg, max_batch=2, page_size=8, num_pages=32,
                max_seq_len=64, eos_token_id=-1,
                max_prefill_tokens=max_prefill,
            ),
            params=params,
        )
        eng.add_request(prompt, max_new_tokens=1, want_prompt_logprobs=True)
        done = []
        while eng.has_work():
            done.extend(eng.step())
        (req,) = done
        assert req.prompt_logprobs[0] is None
        got = req.prompt_logprobs
        assert len(got) == len(ref)
        np.testing.assert_allclose(
            [g for g in got[1:]], [r for r in ref[1:]], rtol=2e-3, atol=2e-3,
        )


def test_missing_layer_slice_rejected(tmp_path):
    """A checkpoint that supplies some layers of a stacked weight but not
    all must fail per-slice, not pass the whole-key check and serve
    zero-initialized layers (ADVICE r4: whole-key-only completeness)."""
    d, _ = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    import safetensors.torch as st
    import os

    fn = next(f for f in os.listdir(d) if f.endswith(".safetensors"))
    sd = st.load_file(os.path.join(d, fn))
    del sd["model.layers.1.mlp.gate_proj.weight"]
    st.save_file(sd, os.path.join(d, fn))
    with pytest.raises(ValueError, match="slices never staged"):
        hf.load_params(d, hf.config_from_hf(d))


def test_missing_declared_shard_rejected(tmp_path):
    """When model.safetensors.index.json declares shard files, every one of
    them must exist before loading starts (a missing shard would otherwise
    just mean fewer tensors iterated)."""
    d, _ = _save(
        tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM
    )
    import json, os

    fn = next(f for f in os.listdir(d) if f.endswith(".safetensors"))
    with open(os.path.join(d, "model.safetensors.index.json"), "w") as f:
        json.dump(
            {
                "weight_map": {
                    "model.embed_tokens.weight": fn,
                    "model.norm.weight": "model-00099-of-00099.safetensors",
                }
            },
            f,
        )
    with pytest.raises(FileNotFoundError, match="00099"):
        hf.load_params(d, hf.config_from_hf(d))
