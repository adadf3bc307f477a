"""Mixtral-style MoE family: serving, sleep/wake, expert-parallel sharding.

The reference serves MoE through vLLM's Mixtral support; this family is the
TPU-native equivalent (models/moe.py) sharing the Llama attention trunk and
the whole engine unchanged (the scanned layer body dispatches its FFN on
the config)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama, moe, quant
from llm_d_fast_model_actuation_tpu.models.registry import (
    init_params_for,
    logical_axes_for,
)


def _cfg(**kw):
    return EngineConfig(
        model=moe.MoeConfig.tiny_moe(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
        **kw,
    )


def test_registry_dispatch():
    mcfg = moe.MoeConfig.tiny_moe()
    params = init_params_for(jax.random.key(0), mcfg)
    assert "router" in params["layers"]
    assert params["layers"]["w_gate"].shape[1] == mcfg.num_experts
    axes = logical_axes_for(mcfg)
    assert axes["layers"]["w_gate"] == ("layers", "expert", "embed", "mlp")
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == mcfg.num_params(), f"declared {mcfg.num_params()} actual {n}"


def test_moe_engine_generates_deterministically():
    eng = InferenceEngine(_cfg(), seed=0)
    a = eng.generate([[1, 2, 3, 4]], max_new_tokens=6)[0]
    b = eng.generate([[1, 2, 3, 4]], max_new_tokens=6)[0]
    assert a == b and len(a) == 6
    # batching must not change greedy results
    batched = eng.generate([[1, 2, 3, 4], [9, 8, 7]], max_new_tokens=4)
    singles = [
        eng.generate([p], max_new_tokens=4)[0] for p in ([1, 2, 3, 4], [9, 8, 7])
    ]
    assert batched == singles


def test_moe_routing_is_input_dependent():
    """Different tokens must pick different expert mixes — a constant router
    would make the MoE silently dense."""
    mcfg = moe.MoeConfig.tiny_moe()
    params = init_params_for(jax.random.key(0), mcfg)
    lp = jax.tree.map(lambda x: x[0], params["layers"])  # layer 0
    x = jax.random.normal(
        jax.random.key(3), (8, mcfg.hidden_size), dtype=mcfg.dtype
    )
    logits = (x @ lp["router"]).astype(np.float32)
    top = np.asarray(jax.lax.top_k(logits, mcfg.experts_per_token)[1])
    assert len({tuple(sorted(row)) for row in top}) > 1


def test_moe_sleep_wake_preserves_generation():
    eng = InferenceEngine(_cfg(), seed=0)
    gold = eng.generate([[5, 6, 7]], max_new_tokens=6)[0]
    mgr = attach_sleep(eng)
    mgr.sleep(1)
    mgr.wake_up()
    assert eng.generate([[5, 6, 7]], max_new_tokens=6)[0] == gold


def test_moe_expert_parallel_matches_single_device(devices8):
    """ep=2 sharding (experts split across devices, contraction over E is a
    psum over ep) must not change greedy generation."""
    from llm_d_fast_model_actuation_tpu.parallel.mesh import MeshPlan, make_mesh

    gold = InferenceEngine(_cfg(), seed=0).generate(
        [[1, 2, 3], [4, 5, 6]], max_new_tokens=5
    )
    mesh = make_mesh(MeshPlan(ep=2), devices8[:2])
    eng = InferenceEngine(_cfg(), mesh=mesh, seed=0)
    wg = eng.params["layers"]["w_gate"]
    assert "ep" in dict(wg.sharding.mesh.shape) and wg.sharding.spec[1] == "ep"
    got = eng.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=5)
    assert got == gold


def test_moe_checkpoint_roundtrip(tmp_path):
    from llm_d_fast_model_actuation_tpu.models import checkpoint

    mcfg = moe.MoeConfig.tiny_moe()
    params = init_params_for(jax.random.key(7), mcfg)
    checkpoint.save_params(str(tmp_path), mcfg, params)
    restored = checkpoint.load_params(str(tmp_path), mcfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )


def test_moe_train_step_decreases_nothing_weird(devices8):
    """train_step runs for the MoE family over a dp x ep mesh (finite loss,
    step increments) — the fine-tune-then-serve loop works for MoE too."""
    from llm_d_fast_model_actuation_tpu.models import train
    from llm_d_fast_model_actuation_tpu.parallel.mesh import (
        MeshPlan,
        make_mesh,
        named_sharding,
        shard_pytree,
    )

    mcfg = moe.MoeConfig.tiny_moe()
    mesh = make_mesh(MeshPlan(dp=2, ep=2), devices8[:4])
    params = shard_pytree(
        init_params_for(jax.random.key(0), mcfg), mesh, logical_axes_for(mcfg)
    )
    opt = train.make_optimizer()
    state = train.make_train_state(params, opt)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, mcfg.vocab_size, (4, 32)).astype(np.int32),
        named_sharding(mesh, ("batch", None)),
    )
    seq_lens = jax.device_put(
        np.full((4,), 32, np.int32), named_sharding(mesh, ("batch",))
    )
    with mesh:
        state2, metrics = jax.jit(
            lambda s, t, sl: train.train_step(s, mcfg, t, sl, opt)
        )(state, tokens, seq_lens)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1


# -- the two forms of the expert layer (models/moe.py:takes_grouped) ------------

PAGE = 16
LIMIT = moe.GROUPED_MIN_ROWS


def _wide(**kw):
    """tiny_moe with room for a segment over the limit."""
    return dataclasses.replace(moe.MoeConfig.tiny_moe(), max_seq_len=1024, **kw)


def _with_router(params, routing):
    """``even``: the seeded router. ``one_takes_all``: every logit 0, so the
    ties give every row to experts 0 and 1 and none to 2 and 3. ``one_empty``:
    experts 1, 2 and 3 share a column, so a row goes to {0, 1} or {1, 2} by
    its own input and expert 3 gets none."""
    router = params["layers"]["router"]
    if routing == "one_takes_all":
        router = jnp.zeros_like(router)
    elif routing == "one_empty":
        router = router.at[..., 2:].set(router[..., 1:2])
    return {**params, "layers": {**params["layers"], "router": router}}


def _served(cfg, params, rows, mesh=None):
    """The three serving forwards, each traced with ``rows`` rows: a cold
    segment, a continued one, and a decode step of ``rows`` sequences."""
    pps = cfg.max_seq_len // PAGE
    pages = max(pps, rows) + 1
    cache = tuple(
        jnp.zeros((cfg.cache_layers, pages, PAGE, cfg.kv_dim), cfg.dtype)
        for _ in range(2)
    )
    toks = jax.random.randint(jax.random.key(5), (rows,), 0, cfg.vocab_size)
    row = 1 + jnp.arange(pps, dtype=jnp.int32)[None]
    lens = jnp.asarray([rows], jnp.int32)
    each = 1 + jnp.arange(rows, dtype=jnp.int32)[:, None]
    return {
        "prefill": lambda p: llama.prefill(
            p, cfg, toks[None], lens, cache, row, mesh=mesh)[0],
        "prefill_continue": lambda p: llama.prefill_continue(
            p, cfg, toks[None], jnp.zeros((1,), jnp.int32), lens, cache, row,
            mesh=mesh)[0],
        "decode_step": lambda p: llama.decode_step(
            p, cfg, toks, jnp.zeros((rows,), jnp.int32), cache, each,
            mesh=mesh)[0],
    }


def _grouped_matmuls(jaxpr):
    """Grouped matmuls in a jaxpr, a scan's body counted once."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "ragged_dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _grouped_matmuls(sub)
    return n


def _run_at(monkeypatch, limit, forward, params):
    """``forward`` traced anew (jit keeps a function's trace) with the
    limit of the rule at ``limit``."""
    monkeypatch.setattr(moe, "GROUPED_MIN_ROWS", limit)
    out = jax.jit(lambda p: forward(p))(params)
    monkeypatch.setattr(moe, "GROUPED_MIN_ROWS", LIMIT)
    return out


@pytest.mark.parametrize("routing", ["even", "one_takes_all", "one_empty"])
@pytest.mark.parametrize("rows", [LIMIT - 1, LIMIT, LIMIT + 16])
def test_grouped_form_equals_dense_form_either_side_of_the_limit(
    rows, routing, monkeypatch
):
    """Each forward traced with ``rows`` rows picks its form by the rule
    (no grouped matmul under the limit, three a layer at it), and the two
    forms are the same top-k weighted sum: logits agree to bfloat16's
    rounding however the rows fall on the experts."""
    cfg = _wide(attention_impl="grouped")
    params = _with_router(init_params_for(jax.random.key(0), cfg), routing)
    for name, forward in _served(cfg, params, rows).items():
        found = _grouped_matmuls(jax.make_jaxpr(forward)(params).jaxpr)
        assert found == (3 if rows >= LIMIT else 0), (name, found)
        grouped = _run_at(monkeypatch, 1, forward, params)
        dense = _run_at(monkeypatch, 10**9, forward, params)
        assert float(jnp.abs(dense).max()) > 1.0
        np.testing.assert_allclose(grouped, dense, atol=0.05, rtol=0.02, err_msg=name)


@pytest.mark.parametrize("case", ["int8", "ep", "tp", "no_stacks"])
def test_what_keeps_the_dense_form(case, devices8):
    """A quantized stack, a mesh that shards the expert or the mlp axis, and
    a caller that brings no stacks stay dense at any row count."""
    from llm_d_fast_model_actuation_tpu.parallel.mesh import MeshPlan, make_mesh

    cfg = _wide()
    params = init_params_for(jax.random.key(0), cfg)
    rows, mesh = LIMIT + 16, None
    assert moe.takes_grouped(cfg, rows, params["layers"]["w_gate"])
    if case == "int8":
        params = quant.quantize_params(params)
    elif case in ("ep", "tp"):
        mesh = make_mesh(MeshPlan(**{case: 2}), devices8[:2])
    if case == "no_stacks":
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        x = jnp.zeros((rows, cfg.hidden_size), cfg.dtype)
        traced = jax.make_jaxpr(lambda lp, x: llama._ffn(cfg, lp, x))(lp, x)
        assert _grouped_matmuls(traced.jaxpr) == 0
        return
    assert not moe.takes_grouped(cfg, rows, params["layers"]["w_gate"], mesh)
    for name, forward in _served(cfg, params, rows, mesh).items():
        assert _grouped_matmuls(jax.make_jaxpr(forward)(params).jaxpr) == 0, name


def test_pallas_grouped_matmul_equals_the_xla_one(monkeypatch):
    """Where the program runs Pallas kernels and the expert widths are whole
    tiles the grouped matmuls are megablox ``gmm`` over the whole stacks,
    the rows padded to whole row tiles; everywhere else ``ragged_dot``. The
    rule at the real widths, and both kernels on one layer (100 assignment
    rows in tiles of 64) against the dense form."""
    pallas = dataclasses.replace(
        moe.MoeConfig.mixtral_8x7b(), attention_impl="pallas")
    assert moe._gmm_fits(pallas)
    assert not moe._gmm_fits(dataclasses.replace(pallas, attention_impl="grouped"))
    assert not moe._gmm_fits(
        dataclasses.replace(pallas, hidden_size=2560, intermediate_size=768))
    monkeypatch.setattr(moe, "GMM_TILES", (64, 32, 32))
    cfg = moe.MoeConfig.tiny_moe()
    kernels = dataclasses.replace(cfg, attention_impl="pallas")
    assert moe._gmm_fits(kernels) and not moe._gmm_fits(cfg)
    stacks = init_params_for(jax.random.key(0), cfg)["layers"]
    x = jax.random.normal(jax.random.key(1), (50, cfg.hidden_size), cfg.dtype)
    for layer in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer], stacks)
        whole = {**lp, **{name: stacks[name] for name in moe.EXPERT_STACKS}}
        dense = moe.moe_ffn(cfg, lp, x)
        for c in (kernels, cfg):
            traced = jax.make_jaxpr(
                lambda x: moe.routed_ffn(c, whole, x, layer=jnp.int32(layer)))(x)
            assert (_grouped_matmuls(traced.jaxpr) == 0) == (c is kernels)
            got = moe.routed_ffn(c, whole, x, layer=jnp.int32(layer))
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(dense, np.float32),
                atol=0.02, rtol=0.02)


def test_looped_stack_indexes_the_expert_stacks_by_layer(monkeypatch):
    """Under ``loop_steps`` 2 the scan hands a layer function the CACHE layer
    (pass x layers + layer); the whole expert stacks are indexed by the
    stack's layer. A config cannot say this yet (``__post_init__`` keeps the
    loop to the dense family), so the field is set past it."""
    cfg = _wide()
    object.__setattr__(cfg, "loop_steps", 2)
    assert cfg.cache_layers == 2 * cfg.num_layers
    params = init_params_for(jax.random.key(0), cfg)
    forward = _served(cfg, params, LIMIT)["prefill"]
    grouped = _run_at(monkeypatch, LIMIT, forward, params)
    dense = _run_at(monkeypatch, 10**9, forward, params)
    np.testing.assert_allclose(grouped, dense, atol=0.05, rtol=0.02)
    # the control: indexed by cache layer, the second pass reads past the
    # stack's end and the logits are another model's
    monkeypatch.setattr(
        llama, "_scanned_ffn",
        lambda cfg, params, lp, li, h, mesh: llama._ffn(
            cfg, lp, h, layer=li, stacks=params["layers"], mesh=mesh),
    )
    wrong = _run_at(monkeypatch, LIMIT, forward, params)
    assert float(jnp.abs(wrong - dense).max()) > 0.5


@pytest.mark.parametrize("family", ["mixtral", "dense"])
def test_engine_counts_the_tokens_it_routed(family):
    """``/v1/stats.moe.routed_tokens``: the tokens of the programs whose
    expert layers ran grouped, by the rule and the bucket dispatched. A
    600-token prompt in segments of at most 512 is one segment over the
    limit and one of 88 under it; a 20-token prompt and every decode step
    are under it. (SmallThinker, which always routes, counts every token:
    tests/test_smallthinker.py.)"""
    if family == "mixtral":
        model = _wide()
    else:
        model = dataclasses.replace(llama.LlamaConfig.tiny(), max_seq_len=1024)
    eng = InferenceEngine(
        EngineConfig(
            model=model, max_batch=2, page_size=PAGE, num_pages=96,
            max_seq_len=1024, max_prefill_tokens=512, decode_chunk=4,
        ),
        seed=0,
    )
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(0, 256, size=n))) for n in (600, 20)]
    eng.generate(prompts, max_new_tokens=6)
    stats = eng.cache_stats()["moe"]
    tokens = sum(len(p) + 5 for p in prompts)
    if family == "mixtral":
        assert stats == {
            "experts": 4, "experts_held": 4, "router_width": 4,
            "tokens": tokens, "routed_tokens": 512,
            "assignments": tokens * 2 * 2,
        }
    else:
        assert stats == {
            "experts": 0, "experts_held": 0, "router_width": 0, "tokens": 0,
            "routed_tokens": 0, "assignments": 0}
