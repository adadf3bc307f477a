"""A step's prompts are dispatched one ahead (``engine.py:_prefill_waiting``):
request i+1 is admitted and dispatched before request i's results are read.
The programs, their order on the device and every token are those of the
loop that fetched each prompt before it looked at the next, which
``_serial_prefill_waiting`` below keeps to the letter as the yardstick."""

from unittest import mock

import jax
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.utils import tracing


def _engine(**kw) -> InferenceEngine:
    """Four slots; prompts past 32 tokens prefill in segments; buckets of
    16 and 32 rows."""
    options = dict(
        model=llama.LlamaConfig.tiny(),
        max_batch=4,
        page_size=8,
        num_pages=128,
        max_seq_len=128,
        decode_chunk=4,
        max_prefill_tokens=32,
        prefix_caching=False,
    )
    return InferenceEngine(EngineConfig(**{**options, **kw}), seed=0)


def _serial_prefill_waiting(self, finished):
    """The admission loop before the pipeline, as it stood."""
    while self._waiting:
        req = self._waiting[0]
        if not self._try_admit(req):
            break
        self._waiting.pop(0)
        self._run_prefill(req)
        if req.done:
            self._retire(req)
            finished.append(req)


def _serial(eng: InferenceEngine) -> InferenceEngine:
    eng._prefill_waiting = _serial_prefill_waiting.__get__(eng)
    return eng


def _record_programs(eng: InferenceEngine) -> list:
    """Every dispatch of a compiled program, in the order the device's
    queue gets them: (name, bucket) of a prompt program, ("chunk", T)."""
    calls = []
    call_program, dispatch_chunk = eng._call_program, eng._dispatch_chunk

    def recording_call(program, bucket, *args):
        calls.append((program, bucket))
        return call_program(program, bucket, *args)

    def recording_chunk(running):
        out = dispatch_chunk(running)
        calls.append(("chunk", out[-1], tuple(sorted(running))))
        return out

    eng._call_program = recording_call
    eng._dispatch_chunk = recording_chunk
    return calls


def _drain(eng: InferenceEngine, done: dict) -> None:
    while eng.has_work():
        for req in eng.step():
            assert req.seq_id not in done, "retired twice"
            done[req.seq_id] = req


def _together(eng: InferenceEngine, requests) -> list:
    """All queued before the first step."""
    ids = [eng.add_request(**r) for r in requests]
    done: dict = {}
    _drain(eng, done)
    return [done[i] for i in ids]


def _one_a_step(eng: InferenceEngine, requests) -> list:
    """One queued before each step: no prompt ever follows another."""
    ids, done = [], {}
    for r in requests:
        ids.append(eng.add_request(**r))
        for req in eng.step():
            done[req.seq_id] = req
    _drain(eng, done)
    return [done[i] for i in ids]


def _said(req):
    return req.out_tokens, req.out_logprobs, req.prompt_logprobs, req.finish_reason


def _prompt(n: int, salt: int) -> list:
    return [int(t) for t in (np.arange(n) * 7 + salt) % 250 + 1]


TRAFFIC = {
    "greedy": [
        dict(prompt=_prompt(5, 1), max_new_tokens=9),
        dict(prompt=_prompt(12, 2), max_new_tokens=6),
        dict(prompt=_prompt(3, 3), max_new_tokens=11),
        dict(prompt=_prompt(9, 4), max_new_tokens=5),
    ],
    "seeded_temperature": [
        dict(prompt=_prompt(6, 5), max_new_tokens=8, temperature=0.9, seed=11),
        dict(prompt=_prompt(6, 5), max_new_tokens=8, temperature=0.9, seed=12),
        dict(prompt=_prompt(10, 6), max_new_tokens=7, temperature=0.7,
             top_p=0.8, seed=13),
    ],
    "prompt_logprobs": [
        dict(prompt=_prompt(7, 7), max_new_tokens=5),
        dict(prompt=_prompt(11, 8), max_new_tokens=5,
             want_prompt_logprobs=True, want_top_logprobs=True),
        dict(prompt=_prompt(4, 9), max_new_tokens=5),
    ],
    # 80 tokens = three segments of the suffix program, beside one-segment
    # prompts before and after it
    "long_beside_short": [
        dict(prompt=_prompt(4, 10), max_new_tokens=6),
        dict(prompt=_prompt(80, 11), max_new_tokens=6,
             want_prompt_logprobs=True),
        dict(prompt=_prompt(20, 12), max_new_tokens=6),
    ],
}


@pytest.fixture(scope="module")
def engines():
    """(pipelined, one a step, serial loop): three engines of one seed."""
    return _engine(), _engine(), _serial(_engine())


@pytest.mark.parametrize("mix", list(TRAFFIC))
def test_queued_together_says_what_one_a_step_says(engines, mix):
    piped, stepped, _ = engines

    def overlapped():
        return tracing.phase_stats()["prefills_overlapped"]

    before = overlapped()
    together = _together(piped, TRAFFIC[mix])
    assert overlapped() - before == len(TRAFFIC[mix]) - 1
    before = overlapped()
    alone = _one_a_step(stepped, TRAFFIC[mix])
    assert overlapped() == before  # no prompt follows another in a step
    for got, want in zip(together, alone):
        assert _said(got) == _said(want)
    assert piped.allocator.available == piped.cfg.num_pages - 1


@pytest.mark.parametrize("mix", list(TRAFFIC))
def test_programs_and_their_order_are_the_serial_loops(engines, mix):
    piped, _, serial = engines
    got_calls, want_calls = _record_programs(piped), _record_programs(serial)
    try:
        got = _together(piped, TRAFFIC[mix])
        want = _together(serial, TRAFFIC[mix])
    finally:
        for eng in (piped, serial):
            del eng._call_program, eng._dispatch_chunk
    assert got_calls == want_calls
    assert {c[0] for c in got_calls} >= {"chunk"}
    assert [_said(r) for r in got] == [_said(r) for r in want]
    if mix == "long_beside_short":
        assert [c[:2] for c in got_calls[:5]] == [
            ("prefill", 16), ("suffix_plp", 32), ("suffix_plp", 32),
            ("suffix_plp", 16), ("prefill", 32),
        ]


def test_a_request_that_ends_on_its_first_token_retires_once_in_mid_queue():
    """`max_new_tokens=1` in mid-queue and an eos met on the first token:
    each finishes in the step that admitted it, once, and its slot and
    pages are free when the step returns."""
    probe = _engine()
    first = probe.generate([_prompt(6, 21)], max_new_tokens=1)[0][0]
    eng = _engine(eos_token_id=first)
    requests = [
        dict(prompt=_prompt(5, 20), max_new_tokens=6, ignore_eos=True),
        dict(prompt=_prompt(6, 21), max_new_tokens=6),  # eos at once
        dict(prompt=_prompt(7, 22), max_new_tokens=1, ignore_eos=True),
        dict(prompt=_prompt(8, 23), max_new_tokens=6, ignore_eos=True),
    ]
    ids = [eng.add_request(**r) for r in requests]
    finished = eng.step()
    assert [r.seq_id for r in finished] == ids[1:3]
    assert [r.finish_reason for r in finished] == ["stop", "length"]
    assert finished[0].out_tokens == [first] and len(finished[1].out_tokens) == 1
    assert all(r.slot == -1 for r in finished)
    assert sum(s is not None for s in eng._slots) == 2
    done = {r.seq_id: r for r in finished}
    _drain(eng, done)
    assert sorted(done) == ids
    assert eng.allocator.available == eng.cfg.num_pages - 1
    # the serial loop says the same, token for token
    want = _together(_serial(_engine(eos_token_id=first)), requests)
    assert [_said(done[i]) for i in ids] == [_said(r) for r in want]


def test_a_full_batch_blocks_the_next_admission_until_a_slot_is_free():
    """Five waiting for four slots with the second ending on its first
    token: it is fetched after the third is dispatched, so its slot goes
    to the fifth within the same step, as the serial loop gives it."""
    eng = _engine()
    requests = [
        dict(prompt=_prompt(4 + i, 30 + i), max_new_tokens=1 if i == 1 else 6)
        for i in range(5)
    ]
    blocked = tracing.phase_stats()["admit_blocked"]
    got = _together(eng, requests)
    want = _together(_serial(_engine()), requests)
    assert [_said(r) for r in got] == [_said(r) for r in want]
    assert tracing.phase_stats()["admit_blocked"] - blocked == 0
    assert eng.allocator.available == eng.cfg.num_pages - 1


def test_a_last_free_slot_held_by_an_unfetched_first_token_costs_one_tick():
    """What `_admit` of i+1 may not assume: that i has retired. The fourth
    slot's request ends on its first token, which is read only after the
    fifth was refused: the fifth waits one step where the serial loop
    admitted it at once, and says the same."""
    eng = _engine()
    requests = [
        dict(prompt=_prompt(4 + i, 40 + i), max_new_tokens=1 if i == 3 else 6)
        for i in range(5)
    ]
    ids = [eng.add_request(**r) for r in requests]
    blocked = tracing.phase_stats()["admit_blocked"]
    done = {r.seq_id: r for r in eng.step()}
    assert list(done) == [ids[3]]
    assert [r.seq_id for r in eng._waiting] == [ids[4]]
    assert tracing.phase_stats()["admit_blocked"] - blocked == 1
    _drain(eng, done)
    want = _together(_serial(_engine()), requests)
    assert [_said(done[i]) for i in ids] == [_said(r) for r in want]


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
def test_shared_prefix_queued_together_hits_pages_not_yet_filled(sampling):
    """With the prefix cache on, the second request matches pages that
    the first registered at dispatch and the device has not filled when
    the second is dispatched: the pool's threading orders the two."""
    how = (
        dict(temperature=0.8, seed=5) if sampling == "seeded" else {}
    )
    shared = _prompt(24, 50)
    requests = [
        dict(prompt=shared + _prompt(20, 51), max_new_tokens=7, **how),
        dict(prompt=shared + _prompt(3, 52), max_new_tokens=7, **how),
        dict(prompt=shared + _prompt(20, 51), max_new_tokens=7, **how),
    ]
    eng = _engine(prefix_caching=True)
    calls = _record_programs(eng)
    got = _together(eng, requests)
    assert [r.cached_tokens for r in got] == [0, 24, 40]
    assert [c[:2] for c in calls[:4]] == [
        ("suffix", 32), ("suffix", 16), ("suffix", 16), ("suffix", 16),
    ]
    want = _together(_serial(_engine(prefix_caching=True)), requests)
    assert [r.cached_tokens for r in want] == [0, 24, 40]
    assert [_said(r) for r in got] == [_said(r) for r in want]
    cold = _together(_engine(), requests)
    assert [r.out_tokens for r in got] == [r.out_tokens for r in cold]
    assert got[0].out_tokens == got[2].out_tokens


def test_nothing_is_unfetched_between_steps_and_sleep_wake_round_trips():
    """After every `step()` the engine is what an admin call may act on:
    no chunk in flight, every admitted request past its first token, and
    a level-1 sleep and wake between two steps changes no token."""
    requests = TRAFFIC["greedy"] + TRAFFIC["long_beside_short"]
    gold = _together(_engine(), requests)
    eng = _engine()
    mgr = attach_sleep(eng)
    ids = [eng.add_request(**r) for r in requests[:3]]
    done: dict = {}
    steps = 0
    while eng.has_work():
        for req in eng.step():
            done[req.seq_id] = req
        steps += 1
        assert not eng.chunk_in_flight
        for req in eng._slots:
            assert req is None or (req.out_tokens and req.pos >= len(req.prompt))
        if steps == 1:
            mgr.sleep(1)
            assert eng.params is None
            mgr.wake_up()
            ids += [eng.add_request(**r) for r in requests[3:]]
    assert [_said(done[i]) for i in ids] == [_said(r) for r in gold]


@pytest.mark.parametrize("n", [1, 2, 4])
def test_prefills_overlapped_counts_all_but_a_steps_first(n):
    eng = _engine()
    eng.generate([_prompt(4, 60)], max_new_tokens=2)  # compiled
    before = tracing.phase_stats()
    for i in range(n):
        eng.add_request(_prompt(4 + i, 61 + i), max_new_tokens=3)
    eng.step()
    after = tracing.phase_stats()
    assert after["prefills_overlapped"] - before["prefills_overlapped"] == n - 1
    assert (
        after["phase_n"]["prefill_dispatch"]
        - before["phase_n"]["prefill_dispatch"]
        == after["phase_n"]["prefill_fetch"] - before["phase_n"]["prefill_fetch"]
        == n
    )


def test_a_failed_dispatch_leaves_no_half_emitted_request():
    """An exception between a dispatch and its finish: the step raises as
    a failed step does (the service then fails every request), and no
    request holds a token it was not emitted."""
    eng = _engine()
    eng.generate([_prompt(4, 70)], max_new_tokens=2)
    ids = [
        eng.add_request(_prompt(4 + i, 71 + i), max_new_tokens=4)
        for i in range(3)
    ]
    call_program, seen = eng._call_program, []

    def failing(program, bucket, *args):
        seen.append(program)
        if len(seen) == 2:
            raise RuntimeError("device lost")
        return call_program(program, bucket, *args)

    eng._call_program = failing
    with pytest.raises(RuntimeError, match="device lost"):
        eng.step()
    live = [r for r in eng._slots if r is not None]
    assert [r.seq_id for r in live] == ids[:2]
    assert all(not r.out_tokens and not r.done for r in live)
    assert [r.seq_id for r in eng._waiting] == [ids[2]]


@pytest.mark.parametrize("backend", ["cpu", "none"])
def test_a_slots_key_is_made_on_the_hosts_backend_and_is_what_it_was(backend):
    """The key's tiny programs and their read-back, launched on the chip,
    would run in launch order behind a dispatched prefill: a step's second
    admission would wait out the first prompt's prefill. They run on the
    CPU backend, or on the default device where a process has no such
    backend, as before; the key is the same bit for bit."""
    from llm_d_fast_model_actuation_tpu.engine import engine as engine_mod

    eng = _engine()
    seeded = eng.add_request(_prompt(5, 80), 4, temperature=0.8, seed=123)
    plain = eng.add_request(_prompt(6, 81), 4, temperature=0.8)
    want = {
        seeded: jax.random.key_data(jax.random.key(123)),
        plain: jax.random.key_data(
            jax.random.fold_in(jax.random.key(eng._seed + 1), plain)
        ),
    }
    made_on, real_devices = [], jax.devices
    real_key, real_fold_in = jax.random.key, jax.random.fold_in

    def no_cpu(name=None):
        if name == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real_devices(name)

    def key(seed):
        made_on.append(jax.config.jax_default_device)
        return real_key(seed)

    def fold_in(k, data):
        made_on.append(jax.config.jax_default_device)
        return real_fold_in(k, data)

    with mock.patch.object(jax.random, "key", key), \
            mock.patch.object(jax.random, "fold_in", fold_in), \
            mock.patch.object(
                jax, "devices", no_cpu if backend == "none" else real_devices
            ):
        host = engine_mod._host_device()
        for req in list(eng._waiting):
            assert eng._try_admit(req)
            np.testing.assert_array_equal(
                eng._slot_keys[req.slot], np.asarray(want[req.seq_id])
            )
    assert host == (None if backend == "none" else real_devices("cpu")[0])
    assert made_on == [host] * 3  # a key; a key and a fold_in
