"""The checkpoint path's kernels compile for a TPU v5e at the Trainer's shapes.

Nothing runs: the installed TPU compiler builds each kernel for a described
(not attached) v5e chip, which refuses what interpret mode accepts — an
unaligned block shape, an unsupported reduction, more VMEM than a kernel
may hold. The shapes are the ones the Trainer hands the kernels for
mamba2-130m at its published widths: every leaf is ``uint8`` bytes, saves
scan 4 KiB blocks, restores verify and scatter 128 KiB pages, and the
largest leaf is an Adam moment of the stacked ``w_in`` (about 323 MB).

The topology is described inside a fixture: only one process at a time may
load the TPU library, so nothing here touches it while modules import.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.blocks import TPU_TILE
from repro.kernels.apply_unpack.ops import apply_unpack_device
from repro.kernels.common import pad_blocks_to_tile
from repro.kernels.dirty_diff.kernel import dirty_diff_blocked
from repro.kernels.flush_pack.ops import flush_pack_device
from repro.kernels.flush_scan.kernel import flush_scan_blocked
from repro.kernels.popcnt_checksum.ops import _popcount_blocks

#: restore page of the Trainer's CheckpointConfig
PAGE = 128 * 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def leaf_sizes():
    """Byte sizes of the largest and the smallest checkpoint leaf of
    mamba2-130m (params and AdamW state), from shapes alone."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.optim import adamw_init
    cfg = get_config("mamba2-130m")
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    state = (params, jax.eval_shape(adamw_init, params))
    sizes = [leaf.size * leaf.dtype.itemsize
             for leaf in jax.tree_util.tree_leaves(state)]
    return {"largest": max(sizes), "smallest": min(sizes)}


def _compile(fn, *args, **static):
    """Lower and compile a jitted kernel entry point for the described chip;
    the Pallas kernel must be in the program, not an XLA fallback."""
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("leaf", ["largest", "smallest"])
def test_flush_pack_compiles(leaf, leaf_sizes, one_chip, no_compile_cache):
    n = leaf_sizes[leaf]
    buf = jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
    _compile(flush_pack_device, buf, buf, block_bytes=TPU_TILE, impl="pallas")


@pytest.mark.parametrize("leaf", ["largest", "smallest"])
def test_popcount_blocks_compiles(leaf, leaf_sizes, one_chip,
                                  no_compile_cache):
    n = leaf_sizes[leaf]
    buf = jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
    _compile(_popcount_blocks, buf, block_bytes=TPU_TILE, impl="pallas")


@pytest.mark.parametrize("leaf", ["largest", "smallest"])
def test_apply_unpack_compiles(leaf, leaf_sizes, one_chip, no_compile_cache):
    k = -(-leaf_sizes[leaf] // PAGE)
    img = jax.ShapeDtypeStruct((k * PAGE,), jnp.uint8, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    exp = jax.ShapeDtypeStruct((k,), jnp.uint32, sharding=one_chip)
    _compile(apply_unpack_device, img, img, idx, exp, block_bytes=PAGE,
             impl="pallas")


@pytest.mark.parametrize("kernel", [dirty_diff_blocked, flush_scan_blocked],
                         ids=["dirty_diff", "flush_scan"])
def test_staged_scan_kernels_compile(kernel, leaf_sizes, one_chip,
                                     no_compile_cache):
    nblocks = pad_blocks_to_tile(-(-leaf_sizes["largest"] // TPU_TILE))
    blocks = jax.ShapeDtypeStruct((nblocks, TPU_TILE // 128, 128), jnp.uint8,
                                  sharding=one_chip)
    _compile(kernel, blocks, blocks)
