"""The serving path's Pallas kernels compile for a TPU v5e at glm4-9b widths.

Nothing runs: each case lowers and compiles for a *described* v5e chip
(`jax.experimental.topologies`), which raises whatever the chip's compiler
would — block shapes it cannot tile, ops Mosaic cannot lower — that
interpret mode on the CPU never sees.  The kernel entry points are called
with ``interpret=False``: ``default_backend()`` still reports the CPU here.

The topology is described in a module-scoped fixture, never at import time:
only one process may hold the TPU library, and every pytest-xdist worker
imports this file.  All cases stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.build import build_grau
from repro.core.folding import fold
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.quant.weights import QuantWeight

# glm4-9b: 32 query heads, 2 KV heads of 128, d_model 4096, d_ff 13696;
# block 16 over a 1024-block pool, 4 decode slots, 256-token prefill chunks
H, KVH, D, BS, NB, SLOTS, CHUNK = 32, 2, 128, 16, 1024, 4, 256
K, N, M = 4096, 13696, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec():
    return build_grau(fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8),
                      mac_range=(-30000, 30000), segments=6, num_exponents=8,
                      mode="apot", bias_mode="lsq").spec


def _compile(fn, *shapes):
    """Compile for the described chip; returns the optimized HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _paged(chip, chunk: int, rows: int, kv_bits: int):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    dp = D // 2 if kv_bits == 4 else D
    pool = s((NB, KVH, BS, dp), jnp.float32 if kv_bits == 16 else jnp.int8)
    exp = s((NB, KVH), jnp.int8) if kv_bits < 16 else None

    def fn(q, kp, vp, bt, start, ke, ve):
        return pa._paged_jit(q, kp, vp, bt, start, None, ke, ve, scale=None,
                             s_in=None, kv_bits=kv_bits, interpret=False)

    return _compile(fn, s((rows, chunk, H, D), jnp.float32), pool, pool,
                    s((rows, 64), jnp.int32), s((rows,), jnp.int32),
                    exp, exp)


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_paged_decode_compiles(one_chip, kv_bits):
    assert "tpu_custom_call" in _paged(one_chip, 1, SLOTS, kv_bits)


@pytest.mark.parametrize("kv_bits", [16, 4])
def test_mq_prefill_compiles(one_chip, kv_bits):
    assert "tpu_custom_call" in _paged(one_chip, CHUNK, 1, kv_bits)


@pytest.mark.parametrize("bits", [8, 4])
def test_matmul_wq_compiles(one_chip, bits):
    kp = K // 2 if bits == 4 else K
    w = QuantWeight(
        q=jax.ShapeDtypeStruct((kp, N), jnp.int8, sharding=one_chip),
        e=jax.ShapeDtypeStruct((K // 512, N), jnp.int8, sharding=one_chip),
        bits=bits, caxis=-2, kdim=K, tile=512)
    x = jax.ShapeDtypeStruct((M, K), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda x, w: ops.matmul_wq(x, w, interpret=False), x, w)
    assert "tpu_custom_call" in hlo


def test_matmul_grau_compiles(one_chip):
    spec = _spec()
    x = jax.ShapeDtypeStruct((M, K), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, N), jnp.int8, sharding=one_chip)
    hlo = _compile(lambda x, w: ops.matmul_grau(x, w, spec, interpret=False),
                   x, w)
    assert "tpu_custom_call" in hlo


def test_grau_compiles(one_chip):
    spec = _spec()
    x = jax.ShapeDtypeStruct((M, N), jnp.int32, sharding=one_chip)
    hlo = _compile(lambda x: ops.grau(x, spec, interpret=False), x)
    assert "tpu_custom_call" in hlo
