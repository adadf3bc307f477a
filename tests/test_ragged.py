"""Ragged paged attention + token-packed mixed-batch serving.

Equivalence discipline (docs/perf.md "Mixed-batch serving"): the packed
path must produce BIT-EXACT greedy outputs vs the bucketed path across
mixed lengths, page boundaries, chunked prefill, and mid-batch admission/
retire edges; sampled outputs carry a logprob tolerance (the mixed
program's attention reduces in a different order than the per-bucket
programs, so logits differ at the last ulp and draws can flip at
near-ties — the same caveat as speculative decoding). The Pallas ragged
kernel must agree with the XLA reference twin (interpreter mode on CPU,
turned on by tests/conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine import exec_pool
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.ops import attention as attn

pytestmark = pytest.mark.ragged

MODEL = llama.LlamaConfig.tiny()
PROMPTS = [
    [1, 2, 3, 4, 5],
    [9, 8, 7],
    [4] * 16,  # exactly two pages at page_size 8 (page-boundary length)
    [7, 6, 5, 4, 3, 2, 1] * 3,
]

def _cfg(packed: bool, **kw) -> EngineConfig:
    base = dict(
        model=MODEL, max_batch=4, page_size=8, num_pages=64, max_seq_len=128
    )
    base.update(kw)
    return EngineConfig(packed_serving=packed, **base)


def _generate(packed: bool, prompts=PROMPTS, max_new=8, **kw):
    eng = InferenceEngine(_cfg(packed, **kw), seed=0)
    return eng.generate(prompts, max_new_tokens=max_new), eng


# -- kernel-level identity ----------------------------------------------------


#: the pool layer the kernel-level tests read (of the scenario's two)
LAYER = 1


def _pack_scenario(key, heads, kv_heads, head_dim, page_size, pages_per_seq):
    """Random two-layer pools in the stored layout + a packed buffer mixing
    a cold prefill segment, a decode row, and a mid-sequence suffix
    segment, with alignment gaps and a padded tail (the engine's packing
    layout)."""
    from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool

    rows = 3
    num_pages = rows * pages_per_seq + 1
    ks = jax.random.split(key, 3)
    shape = PagePool.pool_shape(2, num_pages, page_size, kv_heads, head_dim)
    kp = jax.random.normal(ks[0], shape)
    vp = jax.random.normal(ks[1], shape)
    pt = jnp.asarray(
        np.arange(1, 1 + rows * pages_per_seq, dtype=np.int32).reshape(
            rows, pages_per_seq
        )
    )
    T, B = 40, 8
    max_len = page_size * pages_per_seq
    row_slot = np.full(T, -1, np.int32)
    positions = np.zeros(T, np.int32)
    # seq 0: 11-token prefill segment from position 0 (crosses a page)
    row_slot[0:11] = 0
    positions[0:11] = np.arange(11)
    # seq 1: one decode row at a partial last page
    row_slot[16] = 1
    positions[16] = min(13, max_len - 1)
    # seq 2: 5-token suffix continuation from position 7
    row_slot[24:29] = 2
    positions[24:29] = 7 + np.arange(5)
    q = jax.random.normal(ks[2], (T, heads, head_dim))
    return q, kp, vp, pt, jnp.asarray(row_slot), jnp.asarray(positions), B


def test_ragged_reference_matches_per_sequence_paths():
    """The XLA twin must agree with the per-sequence ops it replaces:
    paged_suffix_attention for segments, paged decode for single rows."""
    q, kp, vp, pt, row_slot, positions, _ = _pack_scenario(
        jax.random.key(0), 4, 2, 16, 8, 4
    )
    out = attn.ragged_paged_attention(
        q, kp, vp, pt, row_slot, positions, LAYER
    )
    # seq 0 prefill segment == suffix attention from start 0
    want0 = attn.paged_suffix_attention(
        q[0:11][None], kp, vp, pt[0:1], jnp.asarray([0], jnp.int32), LAYER
    )[0]
    np.testing.assert_allclose(
        np.asarray(out)[0:11], np.asarray(want0), atol=2e-5, rtol=2e-5
    )
    # seq 2 suffix segment == suffix attention from start 7
    want2 = attn.paged_suffix_attention(
        q[24:29][None], kp, vp, pt[2:3], jnp.asarray([7], jnp.int32), LAYER
    )[0]
    np.testing.assert_allclose(
        np.asarray(out)[24:29], np.asarray(want2), atol=2e-5, rtol=2e-5
    )
    # seq 1 decode row == paged decode attention at seq_len = pos + 1
    want1 = attn.paged_decode_attention(
        q[16:17], kp, vp, pt[1:2],
        jnp.asarray([int(positions[16]) + 1], jnp.int32), LAYER,
    )
    np.testing.assert_allclose(
        np.asarray(out)[16:17], np.asarray(want1), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize(
    "heads,kv_heads,head_dim,page_size,pages_per_seq",
    [
        (4, 2, 16, 8, 4),
        (8, 8, 32, 16, 2),  # MHA (group=1)
        (8, 2, 64, 8, 3),  # GQA 4x
    ],
)
def test_ragged_pallas_matches_reference(
    heads, kv_heads, head_dim, page_size, pages_per_seq
):
    from llm_d_fast_model_actuation_tpu.ops.pallas import (
        ragged_paged_attention_pallas,
    )

    q, kp, vp, pt, row_slot, positions, B = _pack_scenario(
        jax.random.key(1), heads, kv_heads, head_dim, page_size,
        pages_per_seq,
    )
    want = attn.ragged_paged_attention(
        q, kp, vp, pt, row_slot, positions, LAYER
    )
    got = ragged_paged_attention_pallas(
        q, kp, vp, pt, row_slot, positions, LAYER, block_rows=B,
        interpret=True,
    )
    valid = np.asarray(row_slot) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(want)[valid],
        atol=2e-5, rtol=2e-5,
    )
    # padding rows are finite garbage (uniform-masked softmax, same as
    # the reference); FULLY-padded blocks skip the page walk and write
    # zeros — the buffer tail here (rows 32..40) is one such block
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got)[32:] == 0).all()


@pytest.mark.parametrize(
    "heads,kv_heads,head_dim,page_size,pages_per_seq",
    [
        (4, 2, 16, 8, 4),
        (8, 8, 32, 16, 2),  # MHA (group=1)
        (8, 2, 64, 8, 3),  # GQA 4x
    ],
)
def test_ragged_pallas_sharded_matches_twin_tp2(
    heads, kv_heads, head_dim, page_size, pages_per_seq
):
    """The shard_map port on a 2-device CPU mesh (interpret mode): each
    shard runs the single-device kernel over its own head slice of the
    page pool — outputs must match the XLA twin across the same
    GQA/page geometries the single-device identity test covers, and the
    output must come back sharded over the query heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_d_fast_model_actuation_tpu.ops.pallas import (
        ragged_paged_attention_pallas_sharded,
    )
    from llm_d_fast_model_actuation_tpu.parallel.mesh import (
        MeshPlan,
        make_mesh,
    )

    mesh = make_mesh(MeshPlan(dp=1, tp=2), jax.devices()[:2])
    q, kp, vp, pt, row_slot, positions, B = _pack_scenario(
        jax.random.key(3), heads, kv_heads, head_dim, page_size,
        pages_per_seq,
    )
    want = attn.ragged_paged_attention(
        q, kp, vp, pt, row_slot, positions, LAYER
    )
    qs = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    kps = jax.device_put(kp, NamedSharding(mesh, attn.POOL_SPEC))
    vps = jax.device_put(vp, NamedSharding(mesh, attn.POOL_SPEC))
    got = ragged_paged_attention_pallas_sharded(
        mesh, qs, kps, vps, pt, row_slot, positions, LAYER,
        block_rows=B, interpret=True,
    )
    assert got.sharding.spec == P(None, "tp", None)  # heads stay sharded
    valid = np.asarray(row_slot) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(want)[valid],
        atol=2e-5, rtol=2e-5,
    )
    # the dispatcher routes mesh + pallas through the shard_map port
    got2 = attn.ragged_paged_attention(
        qs, kps, vps, pt, row_slot, positions, LAYER, impl="pallas",
        mesh=mesh,
    )
    np.testing.assert_allclose(
        np.asarray(got2)[valid], np.asarray(want)[valid],
        atol=2e-5, rtol=2e-5,
    )


def test_ragged_pallas_bf16_io_fp32_math():
    q, kp, vp, pt, row_slot, positions, B = _pack_scenario(
        jax.random.key(2), 4, 2, 32, 8, 2
    )
    from llm_d_fast_model_actuation_tpu.ops.pallas import (
        ragged_paged_attention_pallas,
    )

    qb, kpb, vpb = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    want = attn.ragged_paged_attention(
        qb, kpb, vpb, pt, row_slot, positions, LAYER
    )
    got = ragged_paged_attention_pallas(
        qb, kpb, vpb, pt, row_slot, positions, LAYER, block_rows=B,
        interpret=True,
    )
    assert got.dtype == jnp.bfloat16
    valid = np.asarray(row_slot) >= 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[valid],
        np.asarray(want, np.float32)[valid],
        atol=3e-2, rtol=3e-2,
    )


# -- engine equivalence: packed vs bucketed -----------------------------------


def test_packed_greedy_bit_exact():
    """The acceptance bar: bit-exact greedy outputs across mixed lengths
    and a page-boundary prompt, prefix caching on."""
    want, _ = _generate(False)
    got, eng = _generate(True)
    assert got == want
    assert eng.packed_steps > 0  # the packed program actually ran


def test_packed_greedy_chunked_prefill_and_long_prompt():
    """Chunked prefill (segments spanning several packed steps) and a
    prompt longer than the small buffer shape."""
    prompts = PROMPTS + [[11, 13, 17, 19] * 12]  # 48 tokens
    want, _ = _generate(False, prompts=prompts, max_prefill_tokens=6)
    got, eng = _generate(True, prompts=prompts, max_prefill_tokens=6)
    assert got == want
    # ... and chunking must not change packed outputs either
    got2, _ = _generate(True, prompts=prompts)
    assert got2 == want


def test_packed_greedy_across_attention_impls():
    """reference / grouped XLA and the Pallas ragged kernel (interpret
    mode) must generate identical greedy tokens through the engine —
    same window as the bucketed cross-impl test (test_pallas_ops):
    per-call agreement is ~1e-5, so a long enough greedy run can hit an
    argmax near-tie; the kernel-identity tests above pin the math."""
    impls = ["reference", "grouped", "pallas"]
    outs = {}
    for impl in impls:
        outs[impl], _ = _generate(True, attention_impl=impl, max_new=6)
    attn.set_attention_impl("reference")
    for impl in impls[1:]:
        assert outs[impl] == outs["reference"], impl


def test_packed_sampled_logprob_tolerance():
    """Sampled (temperature > 0, seeded) requests: the packed program's
    logits differ from the bucketed ones at reduction-order level, so
    draws may flip at near-ties; up to the first divergent token the
    reported logprobs must agree tightly."""
    def run(packed):
        eng = InferenceEngine(_cfg(packed), seed=0)
        ids = [
            eng.add_request(p, 8, temperature=0.8, top_p=0.9, seed=42 + i)
            for i, p in enumerate(PROMPTS)
        ]
        out = {}
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = (r.out_tokens, r.out_logprobs)
        return [out[i] for i in ids]

    ref = run(False)
    got = run(True)
    full_matches = 0
    for (rt, rl), (gt, gl) in zip(ref, got):
        assert len(gt) == len(rt)
        for i in range(len(rt)):
            if rt[i] != gt[i]:
                break  # draws diverged at a near-tie: later tokens differ
            assert abs(rl[i] - gl[i]) < 0.05
        else:
            full_matches += 1
    # the divergence is a near-tie phenomenon, not systematic: at least
    # one stream reproduces end-to-end
    assert full_matches >= 1


def test_packed_mid_batch_admission_and_retire():
    """A short request admitted while a long one is mid-prefill (chunked)
    must ride the same packed steps, finish first (retire edge), and
    leave the long request's output identical to the bucketed run."""
    long_p = [5, 4, 3, 2, 1] * 8  # 40 tokens, chunked at 6/step
    short_p = [1, 2, 3]

    def run(packed):
        eng = InferenceEngine(_cfg(packed, max_prefill_tokens=6), seed=0)
        out = {}
        a = eng.add_request(long_p, 6)
        for _ in range(2):  # long prompt mid-prefill after 2 steps
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        b = eng.add_request(short_p, 2)
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        return out[a], out[b]

    assert run(True) == run(False)


def test_packed_sampling_features_greedy_paths():
    """Penalties, logit bias, stop sequences, and ignore_eos flow through
    the packed program's shared sampling tail identically."""
    def run(packed):
        eng = InferenceEngine(_cfg(packed), seed=0)
        out = {}
        ids = [
            eng.add_request(
                [1, 2, 3, 4], 8, presence_penalty=0.5,
                frequency_penalty=0.3,
            ),
            eng.add_request([9, 8, 7], 8, logit_bias={5: 50.0}),
            eng.add_request([4] * 10, 8, stop_seqs=[(125, 125)]),
            eng.add_request([7, 6, 5], 4, ignore_eos=True),
        ]
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = (r.out_tokens, r.finish_reason)
        return [out[i] for i in ids]

    assert run(True) == run(False)


def test_packed_echo_falls_back_bucketed():
    """want_prompt_logprobs (echo) requests route through the bucketed
    prefill inside a packed engine — exact same prompt logprobs."""
    def run(packed):
        eng = InferenceEngine(_cfg(packed), seed=0)
        rid = eng.add_request([3, 1, 4, 1, 5, 9, 2, 6], 4,
                              want_prompt_logprobs=True)
        other = eng.add_request([2, 7, 1, 8], 4)
        done = {}
        while eng.has_work():
            for r in eng.step():
                done[r.seq_id] = r
        return done[rid], done[other]

    ref_echo, ref_other = run(False)
    got_echo, got_other = run(True)
    assert got_echo.out_tokens == ref_echo.out_tokens
    assert got_echo.prompt_logprobs == ref_echo.prompt_logprobs
    assert got_other.out_tokens == ref_other.out_tokens


def test_packed_top_logprobs_match():
    def run(packed):
        eng = InferenceEngine(_cfg(packed), seed=0)
        rid = eng.add_request([1, 2, 3, 4, 5], 4, want_top_logprobs=True)
        done = {}
        while eng.has_work():
            for r in eng.step():
                done[r.seq_id] = r
        return done[rid]

    ref = run(False)
    got = run(True)
    assert got.out_tokens == ref.out_tokens
    for ra, ga in zip(ref.out_top_logprobs, got.out_top_logprobs):
        assert [t for t, _ in ra] == [t for t, _ in ga]
        for (_, rl), (_, gl) in zip(ra, ga):
            assert abs(rl - gl) < 0.05


def test_packed_off_is_inert():
    """--packed-serving off preserves today's behavior: the packed
    machinery never engages and no packed stats appear."""
    out, eng = _generate(False)
    assert eng.packed_steps == 0
    assert not eng._packed
    assert eng.pad_waste_bytes["packed"] == 0
    assert eng.pad_waste_bytes["bucketed"] > 0  # bucket padding counted


def test_packed_pad_waste_below_bucketed():
    """With mixed prompt lengths the packed layout's alignment padding
    must waste a lower fraction than power-of-two buckets. The budget is
    sized to the expected step load (docs/perf.md "choosing
    token_budget") — an oversized budget pays its tail as padding."""
    prompts = [[1 + i] * n for i, n in enumerate((5, 13, 29, 61))]
    _, eb = _generate(False, prompts=prompts, max_new=4)
    _, ep = _generate(True, prompts=prompts, max_new=4, token_budget=120)

    def frac(eng, path):
        pad = eng.pad_waste_bytes[path]
        valid = eng.dispatch_tokens[path] * eng._pad_token_bytes
        return pad / max(1, pad + valid)

    assert frac(ep, "packed") < frac(eb, "bucketed")


def test_packed_incompatible_with_pipeline_decode():
    with pytest.raises(ValueError):
        InferenceEngine(_cfg(True, pipeline_decode=True), seed=0)


# -- device-resident scheduler state (dirty edges + per-step H2D) -------------
#
# The packed step keeps the [max_batch, vocab] count/bias mirrors ON
# DEVICE between dispatches (the mixed program maintains them, like the
# chunk program always has); host mirrors re-upload only on dirty edges.
# Every edge below must leave greedy outputs bit-exact vs the bucketed
# path — and the steady-state H2D must stay O(rows).


def test_packed_sched_drop_mid_stream_exact():
    """The sleep/wake edge (engine.drop_device_sched_state): dropping
    the device scheduler state mid-generation — with one request still
    mid-chunked-prefill and penalties active — must rebuild bit-exactly
    from the host mirrors on the next dispatch."""
    long_p = [5, 4, 3, 2, 1] * 8  # chunked at 6/step
    short_p = [1, 2, 3]

    def run(packed, drop):
        eng = InferenceEngine(_cfg(packed, max_prefill_tokens=6), seed=0)
        out = {}
        a = eng.add_request(long_p, 6, presence_penalty=0.5)
        b = eng.add_request(short_p, 6, frequency_penalty=0.4)
        for _ in range(2):  # long prompt mid-prefill, short one decoding
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        if drop:
            eng.drop_device_sched_state()
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        return out[a], out[b]

    gold = run(False, drop=False)
    assert run(True, drop=False) == gold
    assert run(True, drop=True) == gold
    assert run(False, drop=True) == gold  # the bucketed edge still holds


def test_packed_penalties_over_cached_prefix_exact():
    """The exact-count edge: a penalty request whose prompt hits the
    prefix cache (its cached tokens never stream through the packed
    buffer) forces the full-mirror re-upload instead of in-program
    accumulation — counts must still cover the whole prompt."""
    shared = [11, 12, 13, 14, 15, 16, 17, 18]  # one full page at size 8

    def run(packed):
        eng = InferenceEngine(_cfg(packed), seed=0)
        out = {}
        first = eng.add_request(shared + [1, 2], 4)
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        # same prefix -> cache hit; penalties must count the cached part
        second = eng.add_request(
            shared + [3, 4], 8, presence_penalty=0.9, frequency_penalty=0.7
        )
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        return out[first], out[second]

    got = run(True)
    assert got == run(False)


def test_packed_bias_admission_mid_stream_exact():
    """The bias edge: a logit_bias request admitted while another stream
    is mid-decode re-uploads the mirrors once; the biased sample and the
    neighbor's decode stay bit-exact vs bucketed."""
    def run(packed):
        eng = InferenceEngine(_cfg(packed), seed=0)
        out = {}
        a = eng.add_request([7, 6, 5, 4], 10)
        for _ in range(2):
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        b = eng.add_request([1, 2, 3], 6, logit_bias={5: 50.0})
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = r.out_tokens
        return out[a], out[b]

    assert run(True) == run(False)


@pytest.mark.parametrize("tp", [1, 2])
def test_packed_steady_state_h2d_o_rows(tp):
    """The headline: steady-state packed decode moves O(rows) H2D per
    step — no [max_batch, vocab] mirror re-upload. With a vocab big
    enough to dominate, the packed path's per-step bytes must be at
    least 10x below what per-step mirror re-uploads (the pre-device-
    resident behavior, and what admission-heavy bucketed serving still
    pays) would cost. The same count holds on a tp=2 mesh, where the
    scheduler arrays are replicated."""
    from llm_d_fast_model_actuation_tpu.parallel.mesh import (
        MeshPlan,
        make_mesh,
    )

    mesh = (
        make_mesh(MeshPlan(dp=1, tp=2), jax.devices()[:2]) if tp == 2 else None
    )
    model = llama.LlamaConfig.tiny(vocab=4096)
    cfg = EngineConfig(
        model=model, max_batch=4, page_size=8, num_pages=64,
        max_seq_len=128, packed_serving=True, token_budget=96,
        prefix_caching=False,
    )
    eng = InferenceEngine(cfg, mesh=mesh, seed=0)
    prompts = [[i + 1, i + 2, i + 3, i + 4, i + 5] for i in range(4)]
    eng.generate(prompts, max_new_tokens=4)  # warm + first full upload
    eng.step_h2d_bytes = {"packed": 0, "bucketed": 0}
    steps0 = eng.packed_steps
    # two waves of admissions mid-decode: every step has prefill work,
    # so the packed program dispatches continuously
    ids = [eng.add_request(p, 8) for p in prompts]
    for _ in range(2):
        eng.step()
    ids += [eng.add_request([9, 8, 7, 6], 8) for _ in range(2)]
    while eng.has_work():
        eng.step()
    packed_steps = eng.packed_steps - steps0
    assert packed_steps >= 2
    spent = eng.step_h2d_bytes["packed"]
    assert spent > 0
    # what the old path paid per packed step: the [b, vocab] counts +
    # bias mirrors alone (ignoring its page-table and small-mirror
    # uploads — being generous to the baseline)
    b, V = cfg.max_batch, model.vocab_size
    mirrors_per_step = b * V * (4 + 4)
    assert spent * 10 <= packed_steps * mirrors_per_step, (
        spent, packed_steps, mirrors_per_step
    )
    # and no full upload happened at all in this window (admissions had
    # no bias / cached-prefix penalties): the total stays under ONE
    # mirror re-upload
    assert spent < mirrors_per_step


# -- warmup plan / exec pool --------------------------------------------------


def test_warmup_plan_packed_compiles_fewer_programs():
    """The acceptance-criteria assert: a packed engine's warmup plan is
    strictly smaller than the bucketed plan for the same buckets — the
    log2(max_seq) prefill/suffix buckets collapse into the one-or-two
    token-budget shapes."""
    from llm_d_fast_model_actuation_tpu.engine.engine import mixed_bucket

    buckets = (16, 32, 64, 128)
    cfg = _cfg(True)
    plan_b = exec_pool.warmup_plan(_cfg(False), buckets)
    plan_p = exec_pool.warmup_plan(cfg, buckets)
    assert len(plan_p) < len(plan_b)
    assert (
        "mixed", mixed_bucket(cfg.packed_token_budget, cfg.pages_per_seq)
    ) in plan_p
    assert not any(p in ("prefill", "suffix") for p, _ in plan_p)
    # both still cover the decode chunks
    assert ("chunk", cfg.decode_chunk) in plan_p


def test_mixed_aot_executables_bit_exact():
    """AOT-compiled mixed executables (the warm-swap path) must dispatch
    bit-identically to first-touch jit. The 70-token prompt drives the
    KV width to the full page-table bucket the warmup compiled, so the
    installed mixed executable is actually exercised."""
    cfg = _cfg(True)
    plan = exec_pool.warmup_plan(cfg, (16,))
    prompts = PROMPTS + [[3, 5, 7] * 24]  # 72 tokens -> full KV width

    def gen(install: bool):
        eng = InferenceEngine(cfg, seed=0)
        if install:
            for prog, bucket in plan:
                compiled = exec_pool.compile_program(cfg, prog, bucket)
                eng.install_executable(prog, bucket, compiled)
        return eng.generate(prompts, max_new_tokens=6)

    assert gen(True) == gen(False)


def test_packed_budget_shapes_and_floor():
    from llm_d_fast_model_actuation_tpu.engine.engine import (
        packed_budget_shapes,
    )
    from llm_d_fast_model_actuation_tpu.ops.attention import RAGGED_BLOCK

    cfg = _cfg(True)
    shapes = packed_budget_shapes(cfg)
    assert 1 <= len(shapes) <= 2
    assert shapes[-1] == cfg.packed_token_budget
    assert all(s % RAGGED_BLOCK == 0 for s in shapes)
    # the floor: every decode slot plus one prefill block must fit
    assert shapes[0] >= RAGGED_BLOCK * (cfg.max_batch + 1)
    # an explicit unaligned budget rounds up
    cfg2 = _cfg(True, token_budget=100)
    assert cfg2.packed_token_budget % RAGGED_BLOCK == 0
    assert cfg2.packed_token_budget >= 100


# -- service level ------------------------------------------------------------


def test_service_packed_metrics_and_span():
    from prometheus_client import generate_latest, REGISTRY

    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )
    from llm_d_fast_model_actuation_tpu.utils import tracing

    args = parse_engine_options(
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64 --packed-serving on --token-budget 64"
    )
    svc = EngineService(args)
    try:
        tracing.enable()
        tracing.clear()
        toks = svc.submit([1, 2, 3, 4, 5], 4, 0.0).result(timeout=120)
        assert len(toks.out_tokens) == 4
        spans = [s.name for s in tracing.snapshot()]
        assert "step.packed" in spans
        exposition = generate_latest(REGISTRY).decode()
        assert "fma_engine_decode_slot_occupancy" in exposition
        assert "fma_engine_packed_tokens_per_step" in exposition
        assert (
            'fma_engine_prefill_pad_waste_bytes_total{model="tiny",'
            'path="packed"}' in exposition
        )
        assert (
            'fma_engine_step_h2d_bytes_total{model="tiny",'
            'path="packed"}' in exposition
        )
    finally:
        svc.shutdown()


def test_service_packed_flag_validation():
    from llm_d_fast_model_actuation_tpu.engine.server import (
        parse_engine_options,
    )

    with pytest.raises(ValueError):
        parse_engine_options(
            "--model tiny --packed-serving on --pipeline-decode on"
        )
    # sharded single-process meshes compose with packed serving now
    args = parse_engine_options(
        "--model tiny --packed-serving on --tensor-parallel-size 2"
    )
    assert args.packed_serving == "on"
    # ... multi-host gangs do not (the lockstep frame can't carry the
    # per-step packing layout)
    with pytest.raises(ValueError):
        parse_engine_options(
            "--model tiny --packed-serving on --num-processes 2 "
            "--process-id 0 --coordinator-address 127.0.0.1:1234"
        )
    with pytest.raises(ValueError):
        parse_engine_options("--model tiny --token-budget -1")
