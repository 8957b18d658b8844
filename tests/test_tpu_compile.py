"""The intersect kernels compile for a TPU v5e that is described, not
attached: the TPU compiler is installed, so Mosaic's layout and VMEM
checks run here at the widths the RMAT scale-16 plan gives the kernels
(rows 4096; candidate widths 32, 256 and 8192; target width 9856), and
at the target bands of the GAP Kron scale-15 plan.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under several
test workers only the worker that runs this file should.  Compiles for
a described device are written to the persistent cache but cannot be
read back without a chip, so the cache is off around them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.intersect.intersect import (
    intersect_pallas,
    intersect_pallas_count,
    intersect_pallas_hits,
)

ROWS = 4096
D_TARG = 9856
HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


KERNELS = {
    "level_split": lambda c, t, lc, lu: intersect_pallas(
        c, t, lc, lu, interpret=False),
    "count": lambda c, t: intersect_pallas_count(c, t, interpret=False),
    "hits": lambda c, t: intersect_pallas_hits(c, t, interpret=False),
}


@pytest.mark.parametrize("d_cand", [32, 256, 8192])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, d_cand):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = [sds((ROWS, d_cand)), sds((ROWS, D_TARG))]
    if kernel == "level_split":
        args += [sds((ROWS, d_cand)), sds((ROWS,))]
    compiled = jax.jit(KERNELS[kernel]).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0 < used < HBM_BYTES


@pytest.mark.parametrize(
    "d_cand,d_targ", [(512, 512), (32, 1280), (1280, 1280), (4096, 6016)]
)
def test_band_shapes_compile_for_v5e(one_chip, d_cand, d_targ):
    """The level-split kernel at the (candidate, target) widths of the
    Kron scale-15 plan's target bands: a 1,280-wide candidate list is
    not a power of two, and 6,016 is the hub band's target width."""
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = [sds((ROWS, d_cand)), sds((ROWS, d_targ)),
            sds((ROWS, d_cand)), sds((ROWS,))]
    compiled = jax.jit(KERNELS["level_split"]).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_count_kernel_compiles_under_shard_map(topo):
    """Algorithm 2's count kernel inside ``jax.shard_map`` on four
    described chips: its output must carry the mesh axis its inputs vary
    over, or ``check_vma`` refuses the trace."""
    mesh = Mesh(np.array(topo.devices), ("p",))
    shard = jax.shard_map(
        lambda c, t: intersect_pallas_count(c, t, interpret=False),
        mesh=mesh, in_specs=(P("p"), P("p")), out_specs=P("p"),
    )

    def sds(width):
        return jax.ShapeDtypeStruct(
            (4 * ROWS, width), jnp.int32,
            sharding=NamedSharding(mesh, P("p")),
        )

    compiled = jax.jit(shard).lower(sds(256), sds(D_TARG)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
