"""Model numerics: prefill/decode consistency, paged-cache correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.models import llama


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def make_cache(cfg, num_pages=32, page_size=8):
    from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool

    shape = PagePool.pool_shape(
        cfg.num_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim
    )
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def test_prefill_shapes(setup):
    cfg, params = setup
    cache = make_cache(cfg)
    tokens = jnp.array([[5, 6, 7, 8, 0, 0, 0, 0]], dtype=jnp.int32)
    seq_lens = jnp.array([4], dtype=jnp.int32)
    table = jnp.array([[1, 2]], dtype=jnp.int32)  # 2 pages of 8 => 16 slots
    logits, cache = llama.prefill(params, cfg, tokens, seq_lens, cache, table)
    assert logits.shape == (1, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    # KV was written into page 1 (first 4 slots), not page 0
    k_pages = cache[0]
    assert float(jnp.abs(k_pages[:, 1, :4]).sum()) > 0
    assert float(jnp.abs(k_pages[:, 1, 4:]).sum()) == 0
    assert float(jnp.abs(k_pages[:, 3:]).sum()) == 0


def test_padding_does_not_change_logits(setup):
    cfg, params = setup
    tokens4 = jnp.array([[5, 6, 7, 8]], dtype=jnp.int32)
    tokens8 = jnp.array([[5, 6, 7, 8, 9, 9, 9, 9]], dtype=jnp.int32)
    lens = jnp.array([4], dtype=jnp.int32)
    c1 = make_cache(cfg)
    c2 = make_cache(cfg)
    table = jnp.array([[1, 2]], dtype=jnp.int32)
    l1, _ = llama.prefill(params, cfg, tokens4, lens, c1, table)
    l2, _ = llama.prefill(params, cfg, tokens8, lens, c2, table)
    np.testing.assert_allclose(
        np.asarray(l1[0, :4]), np.asarray(l2[0, :4]), rtol=2e-2, atol=2e-2
    )


def test_decode_matches_prefill(setup):
    """Gold test: token-by-token decode against the paged cache must produce
    the same logits as one-shot prefill over the full sequence."""
    cfg, params = setup
    seq = [3, 14, 15, 9, 26, 5, 35]
    n = len(seq)

    # one-shot prefill
    cache_a = make_cache(cfg)
    toks = jnp.array([seq + [0]], dtype=jnp.int32)
    table = jnp.array([[1, 2]], dtype=jnp.int32)
    full_logits, _ = llama.prefill(
        params, cfg, toks, jnp.array([n], dtype=jnp.int32), cache_a, table
    )

    # prefill first 3, then decode the rest one token at a time
    cache_b = make_cache(cfg)
    pre = 3
    toks_b = jnp.array([seq[:pre] + [0]], dtype=jnp.int32)
    logits_b, cache_b = llama.prefill(
        params, cfg, toks_b, jnp.array([pre], dtype=jnp.int32), cache_b, table
    )
    np.testing.assert_allclose(
        np.asarray(full_logits[0, pre - 1]),
        np.asarray(logits_b[0, pre - 1]),
        rtol=2e-2,
        atol=2e-2,
    )
    for i in range(pre, n):
        step_logits, cache_b = llama.decode_step(
            params,
            cfg,
            jnp.array([seq[i]], dtype=jnp.int32),
            jnp.array([i], dtype=jnp.int32),
            cache_b,
            table,
        )
        np.testing.assert_allclose(
            np.asarray(full_logits[0, i]),
            np.asarray(step_logits[0]),
            rtol=5e-2,
            atol=5e-2,
        )


def test_batched_decode_isolation(setup):
    """Two sequences in one decode batch must not interact."""
    cfg, params = setup
    cache = make_cache(cfg)
    # seq A in pages 1-2, seq B in pages 3-4
    table = jnp.array([[1, 2], [3, 4]], dtype=jnp.int32)
    toks = jnp.array([[5, 6, 7, 0], [11, 12, 13, 0]], dtype=jnp.int32)
    lens = jnp.array([3, 3], dtype=jnp.int32)
    _, cache = llama.prefill(params, cfg, toks, lens, cache, table)

    logits2, _ = llama.decode_step(
        params,
        cfg,
        jnp.array([8, 14], dtype=jnp.int32),
        jnp.array([3, 3], dtype=jnp.int32),
        cache,
        table,
    )
    # same for seq A alone
    cache_a = make_cache(cfg)
    table_a = jnp.array([[1, 2]], dtype=jnp.int32)
    _, cache_a = llama.prefill(
        params, cfg, toks[:1], lens[:1], cache_a, table_a
    )
    logits_a, _ = llama.decode_step(
        params,
        cfg,
        jnp.array([8], dtype=jnp.int32),
        jnp.array([3], dtype=jnp.int32),
        cache_a,
        table_a,
    )
    np.testing.assert_allclose(
        np.asarray(logits2[0]), np.asarray(logits_a[0]), rtol=2e-2, atol=2e-2
    )


def test_num_params(setup):
    cfg, params = setup
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert total == cfg.num_params()
    assert llama.LlamaConfig.llama3_8b().num_params() == pytest.approx(8.0e9, rel=0.05)
    assert llama.LlamaConfig.llama3_70b().num_params() == pytest.approx(70.6e9, rel=0.05)


def test_gemma_family_forward_and_engine():
    """Gemma-3-style knobs (GeGLU, (1+w) sandwich norms, scaled embeddings,
    QK-norm, tied embeddings) run through the SAME shared forward."""
    import dataclasses

    import jax
    import numpy as np

    from llm_d_fast_model_actuation_tpu.engine import (
        EngineConfig,
        InferenceEngine,
    )
    from llm_d_fast_model_actuation_tpu.models import llama

    cfg = llama.LlamaConfig.tiny_gemma()
    assert cfg.hidden_activation == "gelu" and cfg.post_norms and cfg.qk_norm
    params = llama.init_params(jax.random.key(0), cfg)
    assert "post_attn_norm" in params["layers"]
    assert params["layers"]["q_norm"].shape == (cfg.num_layers, cfg.head_dim)
    # zero-centered norm weights under the (1+w) convention
    assert float(np.abs(np.asarray(params["layers"]["attn_norm"])).max()) == 0.0
    assert "lm_head" not in params  # tied

    eng = InferenceEngine(
        EngineConfig(model=cfg, max_batch=2, page_size=8, num_pages=32, max_seq_len=64),
        seed=0,
    )
    out = eng.generate([[1, 2, 3]], max_new_tokens=5)[0]
    assert len(out) == 5
    # deterministic
    eng2 = InferenceEngine(
        EngineConfig(model=cfg, max_batch=2, page_size=8, num_pages=32, max_seq_len=64),
        seed=0,
    )
    assert eng2.generate([[1, 2, 3]], max_new_tokens=5)[0] == out
    # the knobs actually change the function (vs plain tiny with tied emb)
    plain = dataclasses.replace(
        llama.LlamaConfig.tiny(), tie_embeddings=True
    )
    eng3 = InferenceEngine(
        EngineConfig(model=plain, max_batch=2, page_size=8, num_pages=32, max_seq_len=64),
        seed=0,
    )
    assert eng3.generate([[1, 2, 3]], max_new_tokens=5)[0] != out


def test_gemma_sharded_and_quantized(devices8):
    import dataclasses

    import jax

    from llm_d_fast_model_actuation_tpu.engine import (
        EngineConfig,
        InferenceEngine,
    )
    from llm_d_fast_model_actuation_tpu.models import llama
    from llm_d_fast_model_actuation_tpu.parallel.mesh import MeshPlan, make_mesh

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny_gemma(), quantization="int8"
    )
    mesh = make_mesh(MeshPlan(tp=2), devices8[:2])
    eng = InferenceEngine(
        EngineConfig(model=cfg, max_batch=2, page_size=8, num_pages=32, max_seq_len=64),
        mesh=mesh,
        seed=0,
    )
    out = eng.generate([[4, 5, 6]], max_new_tokens=4)[0]
    assert len(out) == 4


def test_gemma_train_matches_serving_function():
    """forward_train and the serving prefill compute the same function for
    Gemma configs (the (1+w)/sandwich/scaled-embed knobs must not diverge
    between training and serving)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_fast_model_actuation_tpu.models import llama, train

    cfg = llama.LlamaConfig.tiny_gemma()
    params = llama.init_params(jax.random.key(3), cfg)
    tokens = np.array([[5, 6, 7, 8]], dtype=np.int32)
    seq_lens = np.array([4], dtype=np.int32)
    logits_t = train.forward_train(params, cfg, jnp.asarray(tokens), jnp.asarray(seq_lens), remat=False)
    # non-degenerate (the zero-centered norm weights apply as 1+w)
    assert float(jnp.abs(logits_t).max()) > 0

    page_size, num_pages = 8, 16
    cache = make_cache(cfg, num_pages, page_size)
    table = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(1, 8))
    logits_s, _ = llama.prefill(params, cfg, jnp.asarray(tokens), jnp.asarray(seq_lens), cache, table)
    np.testing.assert_allclose(
        np.asarray(logits_t[0, :4]), np.asarray(logits_s[0, :4]),
        rtol=2e-2, atol=2e-2,
    )


def test_num_params_counts_gemma_tensors():
    import jax

    from llm_d_fast_model_actuation_tpu.models import llama

    cfg = llama.LlamaConfig.tiny_gemma()
    params = llama.init_params(jax.random.key(0), cfg)
    total = sum(int(x.size) for x in jax.tree.leaves(params))
    assert total == cfg.num_params()
