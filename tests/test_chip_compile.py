"""Compile both Pallas interaction kernels for a TPU v5e, at the widths of
the full ``md`` twin, without a chip: the TPU compiler is installed, and it
compiles for a described topology. A kernel that passes every interpret-mode
test can still be refused here (layouts Mosaic cannot lower, scalar
prefetch beyond SMEM, more fast memory than a kernel may use).

Widths: V and NP are the 1/10-scale ``md`` build (551,300 people:
1,740,928 visit slots a day, 15,065 schedule tiles) scaled by ten. All
compiles stay in this one file, so that one test worker describes the
topology (it holds the TPU library until it exits).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.interactions import kernel

B = 128
V = 17_409_280  # visit slots a day (V % B == 0)
NP = 150_650  # block-pair tiles in a day's schedule
NB = V // B


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("call", [
    kernel.interactions_pallas_compact_call,
    kernel.interactions_pallas_call,
], ids=["pallas-compact", "pallas"])
def test_kernel_compiles_for_v5e(one_chip, call, traced):
    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    visits = [shape(V, jnp.int32), shape(V, jnp.int32)] + [
        shape(V, jnp.float32) for _ in range(5)]
    sched = [shape(NP, jnp.int32) for _ in range(4)] + [
        shape(NB, jnp.int32), shape(NB, jnp.int32), shape(2, jnp.uint32)]
    src = [shape(V, jnp.float32)] if traced else []

    def run(*args):
        return call(*args[:14], block_size=B, interpret=False,
                    src_val=args[14] if traced else None)

    compiled = jax.jit(run).lower(*visits, *sched, *src).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
