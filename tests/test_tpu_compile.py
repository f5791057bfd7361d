"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Each test lowers one kernel at the real widths of the serving path
(smollm-360m: d_model 960, d_ff 2560, 15 query / 5 KV heads of dim 64) and
compiles it with the TPU compiler for a ``v5e:2x2`` topology that is
described, not attached. That is where Mosaic refuses what interpret mode
accepts: unaligned DMA slices, 1-D vector gathers, vector loads from SMEM.
Nothing runs, so no result or time is checked here.

The kernel wrappers pick Mosaic over interpret mode from the platform; the
``mosaic`` fixture steers them onto the TPU branch for the duration of a
test, since the process itself runs on the CPU.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, ops
from repro.kernels.paged_attention import paged_attention

pytestmark = pytest.mark.kernel

D_MODEL, D_FF = 960, 2560
HEADS, KV_HEADS, HEAD_DIM = 15, 5, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        with pytest.MonkeyPatch.context() as mp:
            # the TPU library reads this once, as it loads: without it the
            # compiler writes its logs under the temp directory
            mp.setenv("TPU_LOG_DIR", "disabled")
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_persistent_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_persistent_cache):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,k,n", [(8, D_MODEL, D_FF), (8, D_FF, D_MODEL),
                                   (128, D_MODEL, KV_HEADS * HEAD_DIM)])
def test_systolic_gemm_compiles(mosaic, one_chip, m, k, n):
    _compile(ops.systolic_matmul, _spec((m, k), jnp.int8, one_chip),
             _spec((k, n), jnp.int8, one_chip))


@pytest.mark.parametrize("approx_k", [0, 4])   # rank 0 and the exact rank 7
@pytest.mark.parametrize("m,k,n", [(8, D_MODEL, D_FF), (128, D_FF, D_MODEL)])
def test_delta_gemm_compiles(mosaic, one_chip, approx_k, m, k, n):
    _compile(lambda a, b: ops.approx_delta_matmul(a, b, k=approx_k),
             _spec((m, k), jnp.int8, one_chip),
             _spec((k, n), jnp.int8, one_chip))


@pytest.mark.parametrize("q_len", [1, 16])     # decode, one prefill chunk
def test_paged_attention_compiles(mosaic, one_chip, q_len):
    b, blk, width = 8, 16, 66                  # 1056 tokens: two KV chunks
    n_pool = b * width + 1
    pool = _spec((n_pool, blk, KV_HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    _compile(lambda q, kp, vp, bt, kvl, qp: paged_attention(
                 q, kp, vp, bt, kvl, qp, chunk=1024),
             _spec((b, q_len, HEADS, HEAD_DIM), jnp.bfloat16, one_chip),
             pool, pool, _spec((b, width), jnp.int32, one_chip),
             _spec((b,), jnp.int32, one_chip),
             _spec((b, q_len), jnp.int32, one_chip))


def test_flash_attention_compiles(no_persistent_cache, one_chip):
    qkv = _spec((1, HEADS, 1024, HEAD_DIM), jnp.bfloat16, one_chip)
    _compile(lambda q, k, v: flash_attention.flash_attention(
                 q, k, v, bq=128, bk=128), qkv, qkv, qkv)


def test_approx_lut_refuses_tpu_naming_delta(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    a = jnp.ones((4, 8), jnp.int8)
    with pytest.raises(NotImplementedError, match="approx_delta"):
        ops.approx_matmul(a, a.T)
