"""Engine under a multi-device mesh: TP sharding + sleep/wake of sharded state."""

import jax
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.parallel.mesh import MeshPlan, make_mesh

@pytest.fixture(scope="module")
def tp2_mesh(devices8):
    return make_mesh(MeshPlan(dp=1, tp=2), devices8[:2])


def make_engine(mesh=None, **overrides):
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
        **overrides,
    )
    return InferenceEngine(cfg, mesh=mesh, seed=0)


def test_tp_sharded_params(tp2_mesh):
    eng = make_engine(tp2_mesh)
    wq = eng.params["layers"]["wq"]
    # heads axis sharded over tp=2
    assert wq.sharding.num_devices == 2
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[-1] == wq.shape[-1] // 2
    # kv pool [L, P, page, kv_heads * head_dim]: the fused axis sharded, a
    # shard holding its kv_heads / tp heads
    kp = eng.pool.k_pages
    m = eng.cfg.model
    assert kp.shape == (m.num_layers, 32, 8, m.num_kv_heads * m.head_dim)
    assert kp.sharding.shard_shape(kp.shape) == kp.shape[:3] + (
        m.num_kv_heads // 2 * m.head_dim,
    )


def test_tp_matches_single_device(tp2_mesh):
    gold = make_engine(None).generate([[5, 6, 7, 8]], max_new_tokens=5)[0]
    got = make_engine(tp2_mesh).generate([[5, 6, 7, 8]], max_new_tokens=5)[0]
    assert got == gold


def test_sharded_sleep_wake(tp2_mesh):
    eng = make_engine(tp2_mesh)
    gold = eng.generate([[3, 1, 4]], max_new_tokens=4)[0]
    mgr = attach_sleep(eng)
    info = mgr.sleep(1)
    assert info["bytes_offloaded"] > 0
    mgr.wake_up()
    # shardings restored identically
    wq = eng.params["layers"]["wq"]
    assert wq.sharding.shard_shape(wq.shape)[-1] == wq.shape[-1] // 2
    assert eng.generate([[3, 1, 4]], max_new_tokens=4)[0] == gold


def test_pipeline_decode_matches_on_tp_mesh(tp2_mesh):
    """Pipelined decode under a TP mesh: identical outputs to sequential
    (the double-buffer must not disturb sharded scheduler state)."""
    prompts = [[5, 6, 7, 8], [2, 4]]
    gold = make_engine(tp2_mesh, decode_chunk=4).generate(
        prompts, max_new_tokens=12
    )
    got = make_engine(
        tp2_mesh, decode_chunk=4, pipeline_decode=True
    ).generate(prompts, max_new_tokens=12)
    assert got == gold


# -- token-packed (mixed-batch) serving on a sharded mesh ---------------------
#
# --packed-serving composes with --tensor-parallel-size: the mixed
# program's ragged attention routes by impl and mesh
# (ops/attention.py:ragged_paged_attention — the Pallas kernel's
# shard_map port for pallas engines, the GSPMD-partitioned XLA twin
# otherwise) and the device-resident scheduler state — counts/bias
# maintained by the program, page table sliced in-program — works
# unchanged on sharded params. These ride the `ragged` CI gate with the
# single-device equivalence suite (tests/test_ragged.py).

MIXED_PROMPTS = [
    [1, 2, 3, 4, 5],
    [9, 8, 7],
    [4] * 16,  # two full pages at page_size 8
    [7, 6, 5, 4, 3, 2, 1] * 3,
]


@pytest.mark.ragged
def test_packed_matches_bucketed_on_tp_mesh(tp2_mesh):
    """The mesh acceptance bar: bit-exact greedy outputs, packed vs
    bucketed, on a 2-device CPU mesh — mixed lengths, a page-boundary
    prompt, and retire/re-admit edges (4 prompts through 2 slots)."""
    gold = make_engine(tp2_mesh).generate(MIXED_PROMPTS, max_new_tokens=8)
    eng = make_engine(tp2_mesh, packed_serving=True)
    got = eng.generate(MIXED_PROMPTS, max_new_tokens=8)
    assert got == gold
    assert eng.packed_steps > 0  # the mixed program actually ran


@pytest.mark.ragged
def test_packed_pallas_shard_map_matches_bucketed_on_tp_mesh(tp2_mesh):
    """The shard_map ragged kernel through the full engine: a pallas
    packed engine on a 2-device CPU mesh (interpret mode) must generate
    bit-exact greedy outputs vs the bucketed mesh engine AND vs the
    single-device pallas packed engine — the mesh acceptance bar for
    the kernel port, mixed lengths and retire/re-admit edges included.
    The packer must keep RAGGED_BLOCK alignment on meshes (each
    shard_map shard replays the same block metadata).

    Window is 6 tokens, matching the single-device cross-impl test
    (test_ragged.py::test_packed_greedy_across_attention_impls): the
    kernel's online softmax and the twin reduce in different orders,
    so a long enough greedy run on the random-init tiny model can hit
    an argmax near-tie (the documented caveat, docs/perf.md); the
    kernel-identity tests pin the math to tolerance."""
    from llm_d_fast_model_actuation_tpu.ops.attention import RAGGED_BLOCK

    gold = make_engine(tp2_mesh).generate(MIXED_PROMPTS, max_new_tokens=6)
    eng = make_engine(
        tp2_mesh, packed_serving=True, attention_impl="pallas"
    )
    assert eng.attention_impl == "pallas"
    assert eng._pack_align == RAGGED_BLOCK
    got = eng.generate(MIXED_PROMPTS, max_new_tokens=6)
    assert got == gold
    assert eng.packed_steps > 0
    single = make_engine(
        None, packed_serving=True, attention_impl="pallas"
    ).generate(MIXED_PROMPTS, max_new_tokens=6)
    assert got == single


@pytest.mark.ragged
def test_packed_mesh_matches_single_device():
    """Packed serving on the mesh must also agree with packed serving on
    one device (the bucketed path already pins this invariant)."""
    mesh = make_mesh(MeshPlan(dp=1, tp=2), jax.devices()[:2])
    gold = make_engine(None, packed_serving=True).generate(
        MIXED_PROMPTS, max_new_tokens=6
    )
    got = make_engine(mesh, packed_serving=True).generate(
        MIXED_PROMPTS, max_new_tokens=6
    )
    assert got == gold


@pytest.mark.ragged
def test_packed_mesh_chunked_prefill_and_features(tp2_mesh):
    """Chunked prefill spanning several packed steps, penalties, and
    stop sequences through the mesh's mixed program — bit-exact vs the
    bucketed mesh run (device-resident counts included: penalties read
    the counts the program maintains on device). Prompt choice matters
    here like in every cross-program greedy test: the random-init tiny
    model sits near argmax ties on degenerate repeat loops, and the
    mixed/chunk programs reduce bf16 in different orders (the
    documented near-tie caveat, docs/perf.md)."""
    def run(packed):
        eng = make_engine(
            tp2_mesh, packed_serving=packed, max_prefill_tokens=6
        )
        out = {}
        ids = [
            eng.add_request([5, 4, 3, 2, 1] * 6, 6,
                            presence_penalty=0.5, frequency_penalty=0.3),
            eng.add_request([2, 7, 1, 8, 2, 8], 8, stop_seqs=[(99, 99)]),
        ]
        while eng.has_work():
            for r in eng.step():
                out[r.seq_id] = (r.out_tokens, r.finish_reason)
        return [out[i] for i in ids]

    assert run(True) == run(False)


@pytest.mark.ragged
def test_packed_mesh_sleep_wake(tp2_mesh):
    """Sleep/wake of a packed mesh engine: the device-resident
    scheduler state is dropped with the client and rebuilt from host
    mirrors on the next dispatch — outputs identical across the cycle,
    shardings restored."""
    eng = make_engine(tp2_mesh, packed_serving=True)
    gold = eng.generate([[3, 1, 4], [1, 5, 9, 2]], max_new_tokens=4)
    mgr = attach_sleep(eng)
    mgr.sleep(1)
    mgr.wake_up()
    assert eng.generate(
        [[3, 1, 4], [1, 5, 9, 2]], max_new_tokens=4
    ) == gold


@pytest.mark.ragged
def test_packed_mesh_warmup_aot_bit_exact(tp2_mesh):
    """AOT executables compiled for the mesh (NamedSharding avals,
    exec_pool.compile_program(mesh=...)) must dispatch bit-identically
    to first-touch jit — the warm-swap path for sharded packed engines.
    The warmup covers the mixed program at FULL page-table width only,
    so the scenario must drive a mixed dispatch there: a 52-token
    prompt chunk-prefilled in 16-token segments puts its final
    segment's rows at positions 48..51 -> kv_pages_bucket = the full
    8-page width; a call counter on the installed executable proves the
    AOT path really served it (entries merely surviving would also be
    true of never-dispatched buckets)."""
    from llm_d_fast_model_actuation_tpu.engine import exec_pool

    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
        packed_serving=True,
        max_prefill_tokens=16,
    )
    plan = exec_pool.warmup_plan(cfg, (16,))
    prompts = MIXED_PROMPTS[:2] + [[3, 5, 7, 9] * 13]  # 52 tokens

    def gen(install: bool):
        eng = InferenceEngine(cfg, mesh=tp2_mesh, seed=0)
        calls = {"mixed": 0}
        if install:
            def counted(fn):
                def wrapper(*args):
                    calls["mixed"] += 1
                    return fn(*args)

                return wrapper

            n = 0
            for prog, bucket in plan:
                compiled = exec_pool.compile_program(
                    cfg, prog, bucket, mesh=tp2_mesh
                )
                eng.install_executable(
                    prog, bucket,
                    counted(compiled) if prog == "mixed" else compiled,
                )
                n += 1
            assert n > 0
        out = eng.generate(prompts, max_new_tokens=6)
        if install:
            # no TypeError/ValueError fallback dropped an entry, and the
            # warmed mixed executable actually dispatched
            assert len(eng._aot) == len(plan)
            assert calls["mixed"] > 0
        return out

    assert gen(True) == gen(False)
