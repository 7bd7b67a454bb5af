"""Compile the served Pallas kernels for a described TPU v5e, no chip.

The TPU compiler (Mosaic, for Pallas) is installed even where no chip
is attached, and refuses what interpret mode accepts: primitives it has
no lowering for (``atan2``, ``asin``), bf16 transcendentals on v5e, and
blocks that are not aligned to the (8, 128) tile.  These compiles take
a couple of seconds each and guard the served kernels at no chip time.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library at a time, and
pytest-xdist workers all import this file.  The detector forwards are
left out (about 1.5 minutes each to compile).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sphere import _sph_nms_batch_device
from repro.kernels.sphiou import sphiou as sphiou_kernel
from repro.kernels.sphiou.ops import sphiou_matrix_batch

STREAMS = 8  # the served pod's NMS batch: one row per stream


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [64, 128])
def test_sphiou_batch_kernel_compiles(one_chip, no_persistent_cache, n):
    """The batched SphIoU kernel at the served NMS-ladder rows."""
    x = _spec((STREAMS, 4, n), one_chip)
    text = sphiou_kernel.sphiou_pallas_batch.lower(
        x, x, block_n=n, block_m=n).compile().as_text()
    assert "tpu_custom_call" in text


def test_sphiou_odd_n_compiles_through_block_clamp(one_chip,
                                                   no_persistent_cache):
    """An odd N pads to the 8-aligned block ``sphiou_matrix_batch``
    clamps to; the wrapper's padding and slicing compile with it."""
    x = _spec((STREAMS, 100, 4), one_chip)
    fn = jax.jit(lambda a: sphiou_matrix_batch(a, a, interpret=False))
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()


def test_device_nms_program_holds_the_kernel(one_chip, no_persistent_cache):
    """The whole served device-NMS program (Pallas SphIoU + greedy
    ``while_loop``) compiles, with the kernel in it."""
    n = 64
    text = _sph_nms_batch_device.lower(
        _spec((STREAMS, n, 4), one_chip), _spec((STREAMS, n), one_chip),
        _spec((STREAMS, n), one_chip, jnp.bool_), _spec((), one_chip),
        interpret=False, use_pallas=True).compile().as_text()
    assert "tpu_custom_call" in text


def test_bf16_sphiou_refused_before_mosaic(one_chip, no_persistent_cache):
    """bf16 SphIoU cannot lower on v5e (no bf16 sin/cos/sqrt): the
    compiled kernel refuses it with a clear error instead of failing
    deep inside Mosaic."""
    x = _spec((STREAMS, 4, 64), one_chip)
    with pytest.raises(ValueError, match="does not lower"):
        sphiou_kernel.sphiou_pallas_batch.lower(
            x, x, block_n=64, block_m=64, dtype=jnp.bfloat16)
