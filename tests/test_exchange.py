import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import exchange as ex


def build_small_plan(W=1, P=12, V=20, seed=0):
    rs = np.random.default_rng(seed)
    visit_person = rs.integers(0, P, (W, V)).astype(np.int32)
    visit_person[:, -3:] = -1  # padding
    owner = (np.arange(P) * W // P).astype(np.int32)
    local = np.zeros(P, np.int32)
    for w in range(W):
        idx = np.flatnonzero(owner == w)
        local[idx] = np.arange(len(idx))
    return ex.build_exchange_plan(visit_person, owner, local), visit_person, owner, local


def test_plan_routes_every_visit():
    plan, vp, owner, local = build_small_plan()
    routed = (plan.send_idx >= 0).sum()
    assert routed == (vp >= 0).sum()
    assert (plan.recv_slot >= 0).sum() == (vp >= 0).sum()


def test_dispatch_combine_single_worker_roundtrip():
    plan, vp, owner, local = build_small_plan()
    P, V = 12, 20
    mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
    vals = jnp.asarray(np.random.default_rng(1).normal(size=(P, 2)).astype(np.float32))

    def f(send, recv, vals):
        vv = ex.dispatch(send, recv, vals, V, "workers")
        back = ex.combine(send, recv, vv, P, "workers")
        return vv, back

    send = jnp.asarray(plan.send_idx[0])
    recv = jnp.asarray(plan.recv_slot[0])
    vv, back = jax.jit(
        jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec(),
                      jax.sharding.PartitionSpec()),
            out_specs=jax.sharding.PartitionSpec(),
            check_vma=False,
        )
    )(send, recv, vals)
    # dispatch: each visit slot got its person's values
    vv = np.asarray(vv)
    for v in range(V):
        pid = vp[0, v]
        if pid >= 0:
            np.testing.assert_allclose(vv[v], np.asarray(vals)[pid], rtol=1e-6)
        else:
            np.testing.assert_allclose(vv[v], 0.0)
    # combine is the adjoint: back[p] = sum over p's visits of visit values
    back = np.asarray(back)
    expect = np.zeros_like(back)
    for v in range(V):
        pid = vp[0, v]
        if pid >= 0:
            expect[pid] += vv[v]
    np.testing.assert_allclose(back, expect, rtol=1e-6)
