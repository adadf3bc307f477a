"""Device release/reacquire on sleep — the TPU time-sharing mechanism.

On TPU a PJRT client holds the chip exclusively, so a sleeping engine that
keeps its client open still blocks every other server (verified empirically:
a second process's client init blocks until the first exits). Release-mode
sleep destroys the client (`engine/device.py`); these tests exercise the
full state machine on the CPU backend (whose client supports the same
destroy/re-create cycle), and the real-chip exclusivity handoff is driven by
`chip_smoke.py` on TPU hardware.

Reference contract: a slept server frees the accelerator for another server
(docs/dual-pods.md:20-56; sleep actuation inference-server.go:1710-1718).
"""

import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.device import (
    reacquire_devices,
    release_devices,
)
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama


def _cfg(**kw):
    return EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
        **kw,
    )


def test_release_and_reacquire_roundtrip():
    """Client destroy + re-create, bare."""
    import jax

    n_before = len(jax.devices())
    release_devices()
    devs = reacquire_devices(timeout_s=30)
    assert len(devs) == n_before
    # compute works on the fresh client
    assert float(jax.numpy.ones((4,)).sum()) == 4.0


def test_sleep_with_release_preserves_generation():
    eng = InferenceEngine(_cfg(), seed=0)
    gold = eng.generate([[5, 6, 7, 8]], max_new_tokens=6)[0]

    mgr = attach_sleep(eng)
    info = mgr.sleep(1, release=True)
    # ``devices_released`` additionally says the client object really
    # died (engine/device.py) — which other tests' interned meshes and
    # fixtures can prevent in this shared process; the subprocess e2e
    # (test_e2e_fullstack time-share) pins that. Here: the state machine.
    assert info["is_sleeping"] and mgr._released
    assert eng.params is None and eng.pool.k_pages is None

    info = mgr.wake_up()
    assert not info["is_sleeping"] and not info["devices_released"]
    assert info["last_reacquire_seconds"] >= 0.0

    again = eng.generate([[5, 6, 7, 8]], max_new_tokens=6)[0]
    assert again == gold, "generation must be bit-identical across release"


def test_release_midstream_resumes():
    """Release-mode sleep in the middle of a generation: KV pages survive the
    numpy round trip and the sequence continues bit-exact."""
    eng = InferenceEngine(_cfg(), seed=0)
    gold = eng.generate([[9, 8, 7]], max_new_tokens=24)[0]

    eng2 = InferenceEngine(_cfg(), seed=0)
    eng2.add_request([9, 8, 7], max_new_tokens=24)
    for _ in range(2):
        eng2.step()
    assert eng2.has_work()
    mgr = attach_sleep(eng2)
    mgr.sleep(1, release=True)
    mgr.wake_up()
    outs = []
    while eng2.has_work():
        outs.extend(eng2.step())
    assert outs[0].out_tokens == gold


def test_release_with_mesh_rebuilds_mesh(fresh_programs):
    """A TP engine across the virtual CPU mesh survives release: the mesh is
    rebuilt on the re-created devices and sharded state is restored (and
    handed to the engine's ProgramSet, so that is its own)."""
    import jax

    from llm_d_fast_model_actuation_tpu.parallel.mesh import MeshPlan, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(MeshPlan(tp=2), jax.devices()[:2])
    eng = InferenceEngine(_cfg(), mesh=mesh, seed=0)
    gold = eng.generate([[1, 2, 3]], max_new_tokens=5)[0]

    mgr = attach_sleep(eng)
    mgr.sleep(1, release=True)
    old_mesh = eng.mesh
    mgr.wake_up()
    assert eng.mesh is not old_mesh, "mesh must be rebuilt on new devices"
    assert tuple(eng.mesh.axis_names) == tuple(old_mesh.axis_names)
    again = eng.generate([[1, 2, 3]], max_new_tokens=5)[0]
    assert again == gold


def test_level2_release_discards_and_reinit():
    eng = InferenceEngine(_cfg(), seed=0)
    eng.generate([[3, 1, 4]], max_new_tokens=3)
    mgr = attach_sleep(eng)
    info = mgr.sleep(2, release=True)
    assert mgr._released and info["bytes_offloaded"] == 0
    assert mgr._host_state is None

    import jax

    from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool

    m = eng.cfg.model

    def reinit():
        params = llama.init_params(jax.random.key(0), m)
        pool = PagePool.create(
            m.num_layers, eng.cfg.num_pages, eng.cfg.page_size,
            m.num_kv_heads, m.head_dim, dtype=m.dtype,
        )
        return {"params": params, "kv": pool.as_tuple()}

    mgr.wake_up(reinit=reinit)
    out = eng.generate([[3, 1, 4]], max_new_tokens=3)[0]
    assert len(out) == 3
