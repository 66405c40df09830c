"""The fused kernel compiled for a described TPU v5e, with no chip attached.

Each case lowers and compiles the production kernel at a real width for
``v5e:2x2`` with the TPU compiler installed here: what Mosaic would
refuse on the chip (a block out of scoped VMEM, a misaligned slice)
fails here, at no chip time.  Nothing runs, so nothing is timed.

The topology is described inside a module fixture (never at import): one
process at a time may load the TPU library, and only the xdist worker
given this file loads it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernel picks interpret mode from tpu_devices_present(); these
    compiles are for the chip.  The persistent cache is off around them
    (a described-chip entry cannot be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(
        "gpu_rscode_tpu.utils.backend.tpu_devices_present", lambda: True
    )
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, w, sharding):
    dt = jnp.uint8 if w == 8 else jnp.uint16
    return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)


@pytest.mark.parametrize(
    "k,p,w,chunk,refold",
    [
        (10, 4, 8, 32 * MiB, "dot"),    # the RS(10,4) encode headline
        (10, 10, 8, 32 * MiB, None),    # decode at p = k = 10
        (4, 2, 8, 1 * MiB, None),       # small k, 1 MiB cells
        (10, 4, 16, 16 * MiB, "sum"),   # wide symbols
        (128, 16, 8, 1 * MiB, None),    # BASELINE's wide stripe
        # LRC(12,2,2) repair of an 89.5 MB fragment, one 64 MiB segment:
        (6, 1, 8, 11184768, None),      # a local group's 1 x 6 rebuild
        (12, 1, 8, 5592320, None),      # a global parity's 1 x 12
        (12, 4, 8, 5592320, None),      # its encode
    ],
)
def test_single_chip_kernel_compiles(topo, on_tpu, k, p, w, chunk, refold):
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    one = SingleDeviceSharding(topo.devices[0])
    cols = chunk // (w // 8)
    A, B = _spec((p, k), w, one), _spec((k, cols), w, one)
    seen = {}
    real = pg._pallas_matmul

    def spy(*args, **kw):
        seen["tile"] = args[3]
        return real(*args, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pg, "_pallas_matmul", spy)
        compiled = jax.jit(
            lambda a, b: pg.gf_matmul_pallas(a, b, w=w, refold=refold)
        ).lower(A, B).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert seen["tile"] == pg.fit_tile(k, p, w, pg.TPU_TILE)
    if k == 10:
        assert seen["tile"] == pg.TPU_TILE  # k=10 keeps 16384
    else:
        assert seen["tile"] % 128 == 0


@pytest.mark.parametrize(
    "k,p,stripe,cols",
    [
        (10, 4, 1, 32 * MiB),   # cols-sharded: no collective
        (10, 4, 2, 32 * MiB),   # stripe-sharded: psum of pre-parity planes
        # Wide stripe, one default 64 MiB segment: the pre-parity int32
        # (128, tile) output block is what overflowed VMEM at tile 16384.
        (128, 16, 4, 64 * MiB // 128),
    ],
)
def test_mesh_kernel_compiles(topo, on_tpu, k, p, stripe, cols):
    from gpu_rscode_tpu.parallel.mesh import COLS, STRIPE
    from gpu_rscode_tpu.parallel.sharded import _sharded_gf_matmul_jit

    mesh = Mesh(
        np.array(topo.devices[:4]).reshape(stripe, 4 // stripe),
        (STRIPE, COLS),
    )
    sharded = stripe > 1
    A = _spec((p, k), 8, NamedSharding(mesh, P(None, STRIPE) if sharded
                                       else P()))
    B = _spec((k, cols), 8,
              NamedSharding(mesh, P(STRIPE if sharded else None, COLS)))
    compiled = _sharded_gf_matmul_jit.lower(
        A, B, mesh=mesh, w=8, strategy="pallas", stripe_sharded=sharded
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == sharded
