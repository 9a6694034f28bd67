"""Ahead-of-time Mosaic compiles of the serving kernels for a described v5e.

No chip is needed: the TPU compiler is installed with jaxlib, and
``topologies.get_topology_desc`` describes a ``v5e:2x2`` host that is not
attached.  Each case lowers one kernel with ``interpret=False`` at
qwen3-1.7b geometry (16 query heads, 8 KV heads, head dim 128, page 16,
8 slots of 1024 tokens, and the benchmark cells' 3072 and 4096) and asserts that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  What the chip's compiler refuses —
block shapes off the (8, 128) tiling, casts Mosaic has no rule for — fails
here, at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and under pytest-xdist every worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import kv_pack
from repro.kernels import paged_attention as PA

B, HQ, HKV, HD, PS = 8, 16, 8, 128, 16
PAGES_PER_SLOT = 1024 // PS
N_PAGES = 1 + B * PAGES_PER_SLOT


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool_specs(kind, sharding, n_pages=N_PAGES):
    if kind == "dense":
        shape = (n_pages, HKV, PS, HD)
        return {"k": _spec(shape, jnp.bfloat16, sharding),
                "v": _spec(shape, jnp.bfloat16, sharding)}
    nb = HD // PA.quant_block(HD)
    codes, scales = (n_pages, HKV, PS, HD // 2), (n_pages, HKV, PS, nb)
    return {"k_codes": _spec(codes, jnp.uint8, sharding),
            "k_scales": _spec(scales, jnp.uint8, sharding),
            "v_codes": _spec(codes, jnp.uint8, sharding),
            "v_scales": _spec(scales, jnp.uint8, sharding)}


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_q,kind,pages", [
    *[pytest.param(n_q, kind, PAGES_PER_SLOT, id=f"{n_q}-{kind}")
      for n_q in (1, 16) for kind in ("mxfp4", "dense")],
    # the benchmark cells' tables: chat 3072 / 16 = 192 columns, rag's
    # prefill 4096 / 16 + the sentinel = 257; decode and prefill chunk rows
    *[pytest.param(n_q, kind, pages, id=f"{n_q}-{kind}-{pages}")
      for pages in (192, 257) for n_q in (1, 128) for kind in ("mxfp4", "dense")],
])
def test_paged_attention_compiles_for_v5e(one_chip, n_q, kind, pages):
    q_shape = (B, HQ, HD) if n_q == 1 else (B, n_q, HQ, HD)
    fn = jax.jit(lambda q, pool, tables, lengths: PA.paged_attention(
        q, pool, tables, lengths, interpret=False))
    compiled = fn.lower(
        _spec(q_shape, jnp.bfloat16, one_chip),
        _pool_specs(kind, one_chip, 1 + B * pages),
        _spec((B, pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    _assert_mosaic(compiled)


def test_kv_quant_pack_compiles_for_v5e(one_chip):
    fn = jax.jit(lambda x: kv_pack.kv_quant_pack(x, interpret=False))
    compiled = fn.lower(_spec((B * HKV * PS, HD), jnp.bfloat16, one_chip)).compile()
    _assert_mosaic(compiled)


def test_kv_dequant_unpack_compiles_for_v5e(one_chip):
    m, nb = B * HKV * PS, HD // kv_pack.GROUP
    fn = jax.jit(lambda c, s: kv_pack.kv_dequant_unpack(c, s, interpret=False))
    compiled = fn.lower(_spec((m, HD // 2), jnp.uint8, one_chip),
                        _spec((m, nb), jnp.uint8, one_chip)).compile()
    _assert_mosaic(compiled)
