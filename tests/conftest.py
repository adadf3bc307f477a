"""Test fixture root: run the suite on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; shardings/collectives are
validated on 8 virtual CPU devices (the same trick the driver's
`dryrun_multichip` uses). Env must be set before jax is first imported.
"""

import faulthandler
import functools
import os
import signal
import sys
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

from llm_d_fast_model_actuation_tpu.engine import engine as _engine  # noqa: E402
from llm_d_fast_model_actuation_tpu.ops import attention as _attn  # noqa: E402
from llm_d_fast_model_actuation_tpu.utils import tracing as _tracing  # noqa: E402

# Pallas kernels run in interpreter mode in this (CPU) suite; the serving
# path never turns it on by itself (ops/attention.py).
_attn.set_pallas_interpret(True)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- one set of compiled programs per configuration per test process ----------
#
# A ProgramSet is built from static configuration only, and jit caches key on
# the identity of the jitted function: a second engine of an old configuration
# would trace, lower and compile every program again. The suite builds such
# engines by the dozen, so in a test process ``InferenceEngine.__init__`` takes
# its ProgramSet from a memo keyed by the constructor's arguments, and jax's
# own cache serves the second engine the first one's executables. The warm-up
# driver (engine/exec_pool.py) builds its own sets as it does in the product.
# What is traced depends on more than the key wherever a test changes a
# trace-time global (``set_pallas_interpret``, a patched model function, a
# patched constant): such a test, any test that counts compiles, and a test
# whose outcome hangs on how long a first dispatch takes, names the
# ``fresh_programs`` fixture (a module-scoped fixture that builds such an
# engine: ``fresh_programs_for_module``).

ProgramSet = _engine.ProgramSet
_shared_programs = functools.lru_cache(maxsize=None)(ProgramSet)
_ENGINE_INIT = _engine.InferenceEngine.__init__.__code__


def _program_set(model_cfg, logprobs_topk, eos_token_id, mesh=None):
    by_engine = sys._getframe(1).f_code is _ENGINE_INIT
    build = _shared_programs if by_engine else ProgramSet
    return build(model_cfg, logprobs_topk, eos_token_id, mesh)


_engine.ProgramSet = _program_set


def _fresh_programs():
    """Every engine built while this holds compiles its own programs, as in
    the product: for tests of the compiles themselves, and tests that change
    what a trace reads besides the ProgramSet's arguments."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(_engine, "ProgramSet", ProgramSet)
        yield


fresh_programs = pytest.fixture()(_fresh_programs)
#: the same for a module-scoped fixture that builds engines
fresh_programs_for_module = pytest.fixture(scope="module")(_fresh_programs)


@pytest.fixture(scope="module", autouse=True)
def _the_benchmarks_tests_compile_their_own(request):
    """``tests/fmabench/`` belongs to the benchmark (BENCHMARK.json ``paths``)
    and cannot name a fixture of this PR's: its in-process tests read the
    compiles of a real engine's first request (``real_stats``), so the whole
    directory keeps the product's one set an engine."""
    if is_the_benchmarks(request.path):
        yield from _fresh_programs()
    else:
        yield


def is_the_benchmarks(path) -> bool:
    return os.path.join(REPO_ROOT, "tests", "fmabench") in map(str, path.parents)


@pytest.fixture
def numpy_host_staging(monkeypatch):
    """Every ``SleepManager`` built while this holds stages a slept state as
    numpy arrays, the staging of a backend without a ``pinned_host`` memory
    space. This jax's CPU backend has one, as the TPU has, and a slept
    model's ``pinned_host`` leaves never enter the ``ChunkStore``
    (``model_pool.intern_tree`` takes numpy leaves alone): a test that asserts
    interning of a slept model (dedup across pooled siblings, the disk-tier
    rebuild) names this fixture before it builds its service. The program's
    probe is not changed; what the backend's own staging does is pinned by
    the ``own_staging`` tests of ``tests/test_delta_swap.py``."""
    from llm_d_fast_model_actuation_tpu.engine import sleep

    monkeypatch.setattr(sleep, "_platform_supports_host_memory", lambda: False)


@pytest.fixture(autouse=True)
def _tracing_switch_as_the_test_found_it():
    """``tracing.enable()`` / ``disable()`` flip a switch of the process: a
    test that leaves it off (the families' sleep-and-wake tests do) must not
    decide whether the next file on this worker finds its spans."""
    was = _tracing.enabled()
    yield
    (_tracing.enable if was else _tracing.disable)()


# -- no test without a time limit of its own ----------------------------------

#: seconds a test's setup, call or teardown may take: about twice the slowest
#: test of the suite. A hang then costs one failure that names the test, and
#: not the run (the driver's command is cut at 1,470 s and then counts only
#: as far as it got).
TEST_TIME_LIMIT_S = 480.0


def _time_limited(item):
    """Run the rest of a runtest hook under ``TEST_TIME_LIMIT_S``: past it,
    every thread's stack goes to stderr and the test fails by name. The
    alarm needs the main thread, where pytest and the xdist workers run
    their tests."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)
    limit = TEST_TIME_LIMIT_S

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        pytest.fail(f"{item.nodeid} ran past its {limit:g} s", pytrace=False)

    was_handler = signal.signal(signal.SIGALRM, on_alarm)
    was_timer = signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, *was_timer)
        signal.signal(signal.SIGALRM, was_handler)


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_time_limited)
pytest_runtest_call = pytest.hookimpl(wrapper=True)(_time_limited)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True)(_time_limited)


def stop_listening_to_compiles() -> None:
    """Undo ``utils/compile_cache.listen()`` for the tests that follow in
    this process: with the listeners on, every compile leaves ``program.*``
    spans in their rings."""
    from jax._src import monitoring

    from llm_d_fast_model_actuation_tpu.utils import compile_cache

    if compile_cache._listening:
        monitoring.unregister_event_listener(compile_cache._on_event)
        monitoring.unregister_event_duration_listener(compile_cache._on_duration)
        monitoring.unregister_event_time_span_listener(compile_cache._on_time_span)
        compile_cache._listening = False


def cpu_subprocess_env(**extra) -> dict:
    """Environment for a CPU-only child process (launcher/requester/engine)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT
    env.update({k: str(v) for k, v in extra.items()})
    return env


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


def wait_http(url: str, timeout: float = 180.0) -> None:
    """Poll `url` until it answers 200 (shared helper for subprocess
    e2e suites driving launcher/engine children over HTTP)."""
    import time

    import requests

    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            r = requests.get(url, timeout=2)
            if r.status_code == 200:
                return
            last = r.status_code
        except requests.RequestException as e:
            last = e
        time.sleep(0.2)
    raise TimeoutError(f"{url} never became healthy: {last}")


def free_port() -> int:
    """An OS-assigned free TCP port (shared helper for subprocess e2e)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def port_free(port: int) -> bool:
    import socket

    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", port))
            return True
        except OSError:
            return False


def build_tiny_bpe_tokenizer_files(dirpath: str, chat_template: str = ""):
    """A real byte-level BPE tokenizer built locally (no network), saved in
    the HF file layout a model directory ships. Shared by the tokenizer,
    HF-import, and full-stack e2e suites so the file layout under test is
    defined exactly once."""
    import transformers
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tk = Tokenizer(models.BPE())
    tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tk.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=320,
        special_tokens=["<s>", "</s>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    tk.train_from_iterator(
        ["hello world", "the quick brown fox", "günther straße"], trainer
    )
    fast = transformers.PreTrainedTokenizerFast(
        tokenizer_object=tk, bos_token="<s>", eos_token="</s>"
    )
    if chat_template:
        fast.chat_template = chat_template
    fast.save_pretrained(dirpath)
    return dirpath


def build_sharded_hf_model_dir(
    dirpath: str,
    max_shard_size: str = "200KB",
    torch_dtype=None,
    **cfg_kw,
):
    """A tiny real HF model directory saved as a MULTI-SHARD safetensors
    checkpoint (model.safetensors.index.json + N shard files) — the
    parallel cold-start loader's unit of work. ``torch_dtype=
    torch.bfloat16`` saves bf16 shards (exercising the loader's
    no-fp32-transient path). Asserts the checkpoint really sharded, so a
    transformers default change can't silently turn these tests into
    single-shard no-ops."""
    import os

    import torch
    import transformers

    cfg = transformers.LlamaConfig(
        **{
            **dict(
                vocab_size=512,
                hidden_size=64,
                intermediate_size=128,
                num_hidden_layers=4,
                num_attention_heads=4,
                num_key_value_heads=2,
                max_position_embeddings=128,
            ),
            **cfg_kw,
        }
    )
    torch.manual_seed(0)
    m = transformers.LlamaForCausalLM(cfg)
    if torch_dtype is not None:
        m = m.to(torch_dtype)
    m.save_pretrained(dirpath, max_shard_size=max_shard_size)
    shards = [f for f in os.listdir(dirpath) if f.endswith(".safetensors")]
    assert len(shards) > 1, f"expected a sharded checkpoint, got {shards}"
    return dirpath


def build_tiny_hf_model_dir(dirpath: str, chat_template: str = "", **cfg_kw):
    """A tiny real HF model directory (config.json + safetensors +
    tokenizer) like the ones vLLM users bring. `cfg_kw` overrides the
    LlamaConfig fields."""
    import torch
    import transformers

    cfg = transformers.LlamaConfig(
        **{
            **dict(
                vocab_size=512,
                hidden_size=32,
                intermediate_size=64,
                num_hidden_layers=2,
                num_attention_heads=2,
                num_key_value_heads=2,
                max_position_embeddings=128,
            ),
            **cfg_kw,
        }
    )
    torch.manual_seed(0)
    transformers.LlamaForCausalLM(cfg).save_pretrained(dirpath)
    build_tiny_bpe_tokenizer_files(dirpath, chat_template)
    return dirpath
