"""Mesh construction and sharding rules on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_d_fast_model_actuation_tpu.parallel.mesh import (
    MeshPlan,
    make_mesh,
    named_sharding,
    plan_for_devices,
    shard_pytree,
    spec_for,
)


def test_plan_for_devices():
    p = plan_for_devices(8)
    assert p.tp == 8 and p.dp == 1 and p.size == 8
    p2 = plan_for_devices(8, tp=2)
    assert p2.dp == 4 and p2.size == 8
    p3 = plan_for_devices(8, tp=2, sp=2)
    assert p3.dp == 2 and p3.size == 8


def test_make_mesh(devices8):
    mesh = make_mesh(MeshPlan(dp=2, tp=4), devices8)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    assert mesh.shape["sp"] == 1


def test_spec_for():
    assert spec_for(("batch", "seq", "embed")) == P("dp", "sp", None)
    assert spec_for(("heads", "head_dim")) == P("tp", None)


def test_shard_pytree(devices8):
    mesh = make_mesh(MeshPlan(dp=2, tp=4), devices8)
    tree = {
        "w": jnp.zeros((16, 8)),
        "b": jnp.zeros((8,)),
    }
    axes = {"w": ("embed", "mlp"), "b": None}
    sharded = shard_pytree(tree, mesh, axes)
    w_sh = sharded["w"].sharding
    assert isinstance(w_sh, NamedSharding)
    assert w_sh.spec == P(None, "tp")
    # replicated bias
    assert sharded["b"].sharding.spec == P()


def test_collective_under_mesh(devices8):
    # psum over tp via shard_map compiles and runs on the virtual mesh
    mesh = make_mesh(MeshPlan(dp=2, tp=4), devices8)
    x = jnp.arange(8.0).reshape(2, 4)
    xs = jax.device_put(x, named_sharding(mesh, ("batch", "heads")))

    def f(block):
        return jax.lax.psum(block, axis_name="tp")

    out = jax.jit(
        jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(P("dp", "tp"),),
            out_specs=P("dp", "tp"),
        )
    )(xs)
    np.testing.assert_allclose(
        np.asarray(out),
        np.repeat(np.asarray(x).sum(axis=1, keepdims=True), 4, axis=1),
    )
