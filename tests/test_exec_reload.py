"""A start reloads its serving programs as executables (PR 39): an engine
that was handed a pool whose spill is trusted asks it for a program at
first touch, before anything is traced (engine.py:_adopt_program). The
CPU is kept out of that by default (exec_pool.spill_supported), so every
test here forces it on for itself with ``FMA_EXEC_SPILL=1``."""

import json
import os
import shutil

import jax
import pytest

from llm_d_fast_model_actuation_tpu.engine import exec_pool
from llm_d_fast_model_actuation_tpu.engine.engine import (
    EngineConfig,
    InferenceEngine,
)
from llm_d_fast_model_actuation_tpu.engine.exec_pool import (
    ExecutablePool,
    exec_key,
    exec_signature,
)
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.utils import compile_cache

# what these tests count is compiles: every engine builds its own programs
pytestmark = [pytest.mark.warmup, pytest.mark.usefixtures("fresh_programs")]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = [[1, 2, 3], [4, 5, 6, 7]]
#: one prefill bucket and one chunk: drain_tail "chunk" keeps T=1 out
PROGRAMS = [("chunk", 4), ("prefill", 16)]
SERVING = ("jit(_prefill)", "jit(chunk)")


def tiny_cfg(**kw):
    base = dict(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8,
        num_pages=32, max_seq_len=64, decode_chunk=4, drain_tail="chunk",
    )
    base.update(kw)
    return EngineConfig(**base)


def _rows():
    """by_program's rows of the two serving programs, zeros if absent."""
    by = compile_cache.stats()["by_program"]
    zero = {"n": 0, "seconds": 0.0, "trace_s": 0.0, "lower_s": 0.0}
    return {name: dict(by.get(name, zero)) for name in SERVING}


def _serve(pool=None, cfg=None):
    """A fresh engine's tokens for PROMPTS (12 each: a prefill, then
    three chunks of which the first follows an upload and the others do
    not), and the engine."""
    eng = InferenceEngine(cfg or tiny_cfg(), seed=0)
    if pool is not None:
        eng.use_exec_pool(pool)
    return eng.generate(PROMPTS, max_new_tokens=12), eng


def _pool(spill_dir):
    return ExecutablePool(budget_bytes=64 << 20, spill_dir=str(spill_dir))


@pytest.fixture(scope="module")
def starts(tmp_path_factory, fresh_programs_for_module):
    """Three engines in one process: the lazy jit, a first start over an
    empty spill directory, a second start over what the first left."""
    mp = pytest.MonkeyPatch()
    mp.setenv("FMA_EXEC_SPILL", "1")
    compile_cache.listen()
    spill = tmp_path_factory.mktemp("exec-pool")
    try:
        at = [_rows()]
        jit_tokens, _ = _serve()
        at.append(_rows())
        reloaded0 = compile_cache.stats()["programs_reloaded"]
        first_tokens, first = _serve(pool_a := _pool(spill))
        at.append(_rows())
        stats_first = compile_cache.stats()
        second_tokens, second = _serve(pool_b := _pool(spill))
        at.append(_rows())
        stats_second = compile_cache.stats()
        yield {
            "spill": spill, "rows": at, "reloaded0": reloaded0,
            "jit": jit_tokens, "first": first_tokens, "second": second_tokens,
            "engines": (first, second), "pools": (pool_a, pool_b),
            "stats": (stats_first, stats_second),
        }
    finally:
        mp.undo()


@pytest.fixture
def spilled(starts, tmp_path, monkeypatch):
    """A copy of the first start's spill directory that a test may break."""
    monkeypatch.setenv("FMA_EXEC_SPILL", "1")
    d = tmp_path / "exec-pool"
    shutil.copytree(starts["spill"], d)
    return d


def test_second_start_traces_and_lowers_no_serving_program(starts):
    _, _, after_first, after_second = starts["rows"]
    for name in SERVING:
        assert after_second[name]["trace_s"] == after_first[name]["trace_s"]
        assert after_second[name]["lower_s"] == after_first[name]["lower_s"]
    first, second = starts["engines"]
    assert sorted(first._aot) == sorted(second._aot) == PROGRAMS
    assert not first._jit_only and not second._jit_only
    assert starts["pools"][0].misses == len(PROGRAMS)
    assert starts["pools"][1].spill_hits == len(PROGRAMS)
    assert starts["pools"][1].spill_errors == 0


def test_reloaded_tokens_equal_the_jit_paths(starts):
    assert starts["first"] == starts["jit"]
    assert starts["second"] == starts["jit"]


def test_a_reload_is_a_request_and_a_hit_with_its_seconds(starts):
    """/v1/stats.compile_cache stays whole: each reloaded program is a
    request, a hit, one of ``programs_reloaded`` and one more ``n`` of its
    program with seconds under ``seconds`` (so in ``backend_s``)."""
    first, second = starts["stats"]
    n = len(PROGRAMS)
    assert first["programs_reloaded"] == starts["reloaded0"]
    assert second["programs_reloaded"] - first["programs_reloaded"] == n
    assert second["requests"] - first["requests"] == n
    assert second["hits"] - first["hits"] == n
    _, _, after_first, after_second = starts["rows"]
    for name in SERVING:
        assert after_second[name]["n"] == after_first[name]["n"] + 1
        assert after_second[name]["seconds"] > after_first[name]["seconds"]
    assert second["backend_s"] > first["backend_s"]


def test_two_chunks_of_one_T_are_one_executable(starts):
    """The lazy jit compiles ``chunk`` twice for one T: after an upload
    the scheduler arrays are uncommitted, after a chunk they are its
    committed outputs, and committed-ness is in jit's key. An executable
    takes either, so with a pool the second chunk neither shadows the
    first nor traces again: one program fewer a start."""
    before, after_jit, after_first, _ = starts["rows"]
    assert after_jit["jit(chunk)"]["n"] - before["jit(chunk)"]["n"] == 2
    assert after_first["jit(chunk)"]["n"] - after_jit["jit(chunk)"]["n"] == 1
    assert starts["pools"][0].spill_errors == 0


@pytest.mark.parametrize("what", ["sources", "num_pages", "jax_version"])
def test_a_changed_identity_misses(starts, monkeypatch, what):
    monkeypatch.setenv("FMA_EXEC_SPILL", "1")
    cfg = tiny_cfg()
    base = exec_signature(cfg)
    assert _pool(starts["spill"]).get(exec_key(base, "prefill", 16)) is not None
    if what == "sources":
        monkeypatch.setattr(exec_pool, "toolchain_digest", lambda: "edited")
    elif what == "num_pages":
        cfg = tiny_cfg(num_pages=40)
    else:
        monkeypatch.setattr(jax, "__version__", jax.__version__ + ".1")
    eng = InferenceEngine(cfg, seed=0)
    eng.use_exec_pool(pool := _pool(starts["spill"]))
    assert eng._exec_signature != base
    for program, bucket in PROGRAMS:
        assert pool.get(exec_key(eng._exec_signature, program, bucket)) is None
    assert pool.spill_hits == 0 and pool.misses == len(PROGRAMS)


def test_an_edit_to_a_model_or_kernel_file_changes_the_digest(tmp_path):
    for rel in ("models/llama.py", "ops/pallas/decode.py", "README.md"):
        os.makedirs(tmp_path / os.path.dirname(rel), exist_ok=True)
        (tmp_path / rel).write_text("x = 1\n")
    seen = [exec_pool.digest_sources(str(tmp_path))]
    (tmp_path / "README.md").write_text("not a source\n")
    assert exec_pool.digest_sources(str(tmp_path)) == seen[0]
    (tmp_path / "models/llama.py").write_text("x = 2\n")
    seen.append(exec_pool.digest_sources(str(tmp_path)))
    (tmp_path / "ops/pallas/decode.py").write_text("x = 2\n")
    seen.append(exec_pool.digest_sources(str(tmp_path)))
    os.rename(tmp_path / "models/llama.py", tmp_path / "models/llama2.py")
    seen.append(exec_pool.digest_sources(str(tmp_path)))
    assert len(set(seen)) == 4
    # the signature holds it, beside jaxlib's and libtpu's versions
    assert exec_pool.PACKAGE_DIR == os.path.join(
        ROOT, "llm_d_fast_model_actuation_tpu"
    )
    assert len(exec_pool.toolchain_digest()) == 16


def test_a_truncated_blob_is_a_miss_counted_and_overwritten(starts, spilled):
    key = exec_key(exec_signature(tiny_cfg()), "prefill", 16)
    pool = _pool(spilled)
    path = pool._spill_path(key)
    whole = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(whole // 2)
    tokens, eng = _serve(pool)
    assert tokens == starts["jit"]
    assert pool.spill_errors == 1
    assert pool.spill_hits == 1 and pool.misses == 1  # chunk hit, prefill not
    assert ("prefill", 16) in eng._aot and not eng._jit_only
    assert os.path.getsize(path) > whole // 2
    assert _pool(spilled).get(key) is not None


def test_wrong_avals_fall_back_to_jit_with_the_donated_cache_intact(
    starts, spilled
):
    """An executable of another bucket under this bucket's key: the call
    refuses its arguments before anything runs, the engine serves through
    jit from then on (the same tokens: the donated pages were untouched)
    and the pool forgets the blob."""
    cfg = tiny_cfg()
    key = exec_key(exec_signature(cfg), "prefill", 16)
    pool = _pool(spilled)
    pool.put(key, exec_pool.compile_program(cfg, "prefill", 32))
    tokens, eng = _serve(pool)
    assert tokens == starts["jit"]
    assert eng._jit_only == {("prefill", 16)}
    assert ("prefill", 16) not in eng._aot and ("chunk", 4) in eng._aot
    assert pool.spill_errors == 1 and key not in pool
    assert not os.path.exists(pool._spill_path(key))
    # and the engine keeps serving through jit without asking again
    assert eng.generate(PROMPTS, max_new_tokens=12) == tokens
    assert pool.spill_errors == 1


def test_an_entry_larger_than_the_budget_still_serves_and_is_spilled(
    starts, tmp_path, monkeypatch
):
    """``--exec-pool-mib`` bounds what the pool keeps in memory: the
    engine's own table holds what it compiled, and the blob is on disk
    for the next start."""
    monkeypatch.setenv("FMA_EXEC_SPILL", "1")
    small = ExecutablePool(budget_bytes=1, spill_dir=str(tmp_path))
    tokens, eng = _serve(small)
    assert tokens == starts["jit"]
    assert sorted(eng._aot) == PROGRAMS and len(small) == 0
    assert len(list(tmp_path.glob("*.exec"))) == len(PROGRAMS)
    again = ExecutablePool(budget_bytes=1, spill_dir=str(tmp_path))
    tokens, eng = _serve(again)
    assert tokens == starts["jit"]
    assert again.spill_hits == len(PROGRAMS) and len(again) == 0
    assert sorted(eng._aot) == PROGRAMS


def test_without_a_trusted_spill_the_engine_stays_on_the_lazy_jit(
    tmp_path, monkeypatch
):
    """Tier-1's engines behave as before: the CPU's serialized
    executables are not trusted by default, a disabled pool never is."""
    monkeypatch.delenv("FMA_EXEC_SPILL", raising=False)
    eng = InferenceEngine(tiny_cfg(), seed=0)
    eng.use_exec_pool(_pool(tmp_path))
    if jax.default_backend() != "tpu":
        assert eng._exec_pool is None
    monkeypatch.setenv("FMA_EXEC_SPILL", "1")
    eng.use_exec_pool(ExecutablePool(budget_bytes=0, spill_dir=str(tmp_path)))
    assert eng._exec_pool is None
    eng.use_exec_pool(None)
    assert eng._exec_pool is None
    assert eng._chunk_fn(4) is eng.programs.chunk(4)


def test_the_service_hands_its_pool_to_the_engine(tmp_path, monkeypatch):
    """No flag turns the reload on: the service's pool follows the compile
    cache's directory, and its engine asks it at first touch."""
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    monkeypatch.setenv("FMA_EXEC_SPILL", "1")
    monkeypatch.setenv("FMA_EXEC_SPILL_DIR", str(tmp_path))
    svc = EngineService(
        parse_engine_options(
            "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
            "--max-model-len 64"
        )
    )
    try:
        assert svc.engine._exec_pool is svc.exec_pool
        svc.submit([1, 2, 3], 1, 0.0).result(timeout=120)
        assert ("prefill", 16) in svc.engine._aot
        assert list(tmp_path.glob("*.exec"))
        assert "programs_reloaded" in svc.stats()["compile_cache"]
    finally:
        svc.shutdown()


def test_the_metric_reads_the_counter_where_stats_has_it():
    with open(os.path.join(
        ROOT, "fmabench", "metrics", "setup_programs_reloaded.json"
    )) as f:
        metric = json.load(f)
    assert metric["kind"] == "stats" and metric["at"] == "open"
    block, key = metric["path"].split(".")
    assert block == "compile_cache" and key in compile_cache.stats()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [
            m for m in json.load(f)["per_layer"]
            if m["name"] == "setup_programs_reloaded"
        ]
    assert entry == [{
        "name": "setup_programs_reloaded", "unit": "programs",
        "better": "higher", "source": "program_counter", "layer": "compile",
        "moves": "setup_s",
        "workloads": [
            "mixtral-8x7b.batch", "mistral-7b.chat",
            "smallthinker-21b.longmix",
        ],
    }]
