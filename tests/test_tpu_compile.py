"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler refuses what Pallas interpret mode accepts: blocks not
aligned to the (8, 128) tiling and kernels over the scoped-VMEM limit.
These tests compile each kernel at the widths ``chip_smoke.py`` runs, for a
``v5e:2x2`` topology described in-process; nothing executes.  The topology
is described inside a fixture, never at import, so every pytest worker
collects the same tests and only the worker that runs this file loads the
TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.partitioner import plan_gemm_partition
from repro.core.runtime import _block_dgemm
from repro.kernels.block_matmul import block_matmul
from repro.kernels.flash_attention import flash_decode_attention


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_matmul_compiles(one_chip, no_compile_cache, dtype):
    n = 8192
    a = _sds((n, n), dtype, one_chip)
    c = _sds((n, n), jnp.float32, one_chip)
    compiled = block_matmul.lower(a, a, c, alpha=1.0, beta=0.5,
                                  block=(512, 512, 512)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_matmul_rejects_block_over_scoped_vmem(one_chip,
                                                     no_compile_cache):
    n = 8192
    a = _sds((n, n), jnp.float32, one_chip)
    with pytest.raises(ValueError, match="36.00 MiB of VMEM"):
        block_matmul.lower(a, a, a, alpha=1.0, beta=0.5,
                           block=(1024, 1024, 1024))
    # the largest fp32 block at the limit still compiles
    compiled = block_matmul.lower(a, a, a, alpha=1.0, beta=0.5,
                                  block=(1024, 512, 512)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_attention_gqa_compiles(one_chip, no_compile_cache):
    B, hkv, G, d, S = 1, 8, 4, 128, 32768
    q = _sds((B, hkv * G, d), jnp.bfloat16, one_chip)
    kv = _sds((B, hkv, S, d), jnp.bfloat16, one_chip)
    length = _sds((B,), jnp.int32, one_chip)
    compiled = flash_decode_attention.lower(q, kv, kv, length,
                                            block_s=512).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_dgemm_compiles_at_main_phase_block(one_chip,
                                                  no_compile_cache):
    """The host tier's block product at the partition chip_smoke's
    40960³ fp32 GEMM gets for an 8 GiB budget fits one chip's HBM."""
    n = 40960
    part = plan_gemm_partition(n, n, n, 8 * 2**30, 4)
    a = _sds((part.bm, n), jnp.float32, one_chip)
    b = _sds((n, part.bn), jnp.float32, one_chip)
    c = _sds((part.bm, part.bn), jnp.float32, one_chip)
    s = _sds((), jnp.float32, one_chip)
    compiled = _block_dgemm.lower(a, b, c, s, s).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30, used


def test_summa_ring_fits_its_working_set_at_the_cells_size(topo,
                                                          no_compile_cache):
    """The MESH tier's ring at 40960³ fp32 HIGHEST on four chips holds no
    more a chip than ``working_set_bytes``, the figure its budget check
    uses: B and C donated, one more B block and a step's product."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.core.runtime import MeshOocRuntime

    n = 40960
    mesh = Mesh(np.array(topo.devices), ("model",),
                axis_types=(AxisType.Auto,))
    rt = MeshOocRuntime(mesh)
    shapes = [_sds((n, n), jnp.float32, s) for s in rt.shardings()]
    scalar = _sds((), jnp.float32, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec()))
    with jax.default_matmul_precision("highest"):
        compiled = rt.program().lower(*shapes, scalar, scalar).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    need = rt.working_set_bytes(n, n, n, 4)
    assert need - 2**20 < used <= need + 2**20, (used, need)
    assert "collective-permute" in compiled.as_text()
