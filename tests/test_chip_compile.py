"""Compile the serving path's Pallas kernels for a TPU v5e, without a chip.

The TPU compiler is installed with JAX; it compiles for a described
``v5e:2x2`` topology, so these tests refuse what interpret mode cannot
see: tiles off the (8, 128) tiling, casts and shape casts the chip has no
lowering for, scalars in vector memory, and kernels over the VMEM limit.
Every compile is at gemma2-2b's published widths and must hold each
native kernel (``tpu_custom_call``) under its public name: a profiler
trace shows a kernel by that name, and the benchmark finds the kernels it
measures by it, so a rename fails here.  Nothing runs: results are checked
by the interpret-mode tests and on the chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the test workers all
import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import qtensor
from repro.core.qtensor import BlockLayout2D, QTensor
from repro.kernels import fwht, mixfp4_attn, mixfp4_quant

D_MODEL, D_FF, VOCAB = 2304, 9216, 256000
H, HKV, DH = 8, 4, 256
PROJECTIONS = [(D_MODEL, 2048), (D_MODEL, 1024), (2048, D_MODEL),
               (D_MODEL, D_FF), (D_FF, D_MODEL)]
DECODE_M, PREFILL_M = 8, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(sharding, k, n):
    return QTensor(_sds(sharding, (k // 2, n), jnp.uint8),
                   _sds(sharding, (k // 16, n // 16), jnp.uint8),
                   _sds(sharding, ()), "mixfp4", BlockLayout2D(16, 16),
                   (k, n), "float32")


def _compile_native(kernels, f, *args):
    """Compile ``f`` for the described chip; its text must hold a native
    kernel named after each of ``kernels``."""
    text = jax.jit(f).lower(*args).compile().as_text()
    for name in [kernels] if isinstance(kernels, str) else kernels:
        assert re.search(rf'%{name}(\.\d+)? = [^\n]*custom_call_target='
                         r'"tpu_custom_call"', text), name


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("k,n", PROJECTIONS + [(D_MODEL, VOCAB)])
def test_w4a16_gemm_compiles(one_chip, m, k, n):
    _compile_native("mixfp4_gemm_w4a16",
                    lambda x, w: qtensor.qmm(x, w, interpret=False),
                    _sds(one_chip, (m, k), jnp.bfloat16),
                    _weight(one_chip, k, n))


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_fused_w4a4_gemm_compiles(one_chip, m, k, n):
    _compile_native(
        "mixfp4_gemm_w4a4_fused",
        lambda x, w: qtensor.qmm(x, w, interpret=False, fuse_act_quant=True,
                                 per_row_act=True),
        _sds(one_chip, (m, k), jnp.bfloat16), _weight(one_chip, k, n))


@pytest.mark.parametrize("per_row", [True, False])
def test_fused_w4a4_variants_compile(one_chip, per_row):
    """With the RHT signs (per-row), and the per-tensor prologue."""
    k, n = D_MODEL, D_FF
    signs = _sds(one_chip, (k,)) if per_row else None
    _compile_native(
        "mixfp4_gemm_w4a4_fused",
        lambda x, w, s: qtensor.qmm(x, w, interpret=False,
                                    fuse_act_quant=True,
                                    per_row_act=per_row, act_rht_signs=s),
        _sds(one_chip, (DECODE_M, k), jnp.bfloat16), _weight(one_chip, k, n),
        signs)


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_two_pass_w4a4_gemm_compiles(one_chip, m):
    k, n = D_FF, D_MODEL
    _compile_native(
        ("mixfp4_quant_rows", "mixfp4_gemm_w4a4"),
        lambda x, w: qtensor.qmm(
            qtensor.quantize_rows(x, pad_to=k, per_row=True,
                                  interpret=False), w, interpret=False),
        _sds(one_chip, (m, k), jnp.bfloat16), _weight(one_chip, k, n))


@pytest.mark.parametrize("m,k,kw", [
    (DECODE_M, D_MODEL, {"per_row": True}),
    (136, D_MODEL, {}),
    (DECODE_M * HKV * 2, DH, {"scale32": 1.0}),     # packed KV rows
])
def test_quant_rows_compiles(one_chip, m, k, kw):
    _compile_native(
        "mixfp4_quant_rows",
        lambda x: mixfp4_quant.mixfp4_quant_rows(x, interpret=False, **kw),
        _sds(one_chip, (m, k)))


@pytest.mark.parametrize("m", [DECODE_M, 136])
def test_fwht_rows_compiles(one_chip, m):
    _compile_native(
        "fwht_rows", lambda x, s: fwht.fwht_rows(x, s, interpret=False),
        _sds(one_chip, (m, D_MODEL)), _sds(one_chip, (D_MODEL,)))


def _kv(sharding, lead):
    return [_sds(sharding, (*lead, HKV, DH // 2), jnp.uint8),
            _sds(sharding, (*lead, HKV, DH // 16), jnp.uint8)] * 2


def test_attention_fixed_slot_compiles(one_chip):
    b, s = 4, 4096
    _compile_native(
        "mixfp4_attn_decode",
        lambda q, kp, ks, vp, vs, ln, w: mixfp4_attn.mixfp4_attn_decode(
            q, kp, ks, vp, vs, ln, window=w, softcap=50.0, interpret=False),
        _sds(one_chip, (b, H, DH), jnp.bfloat16), *_kv(one_chip, (b, s)),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (), jnp.int32))


def test_attention_paged_compiles(one_chip):
    b, s, page = 4, 4096, 16
    pages = 1 + b * s // page
    _compile_native(
        "mixfp4_attn_decode_paged",
        lambda q, kp, ks, vp, vs, bt, ln, w:
        mixfp4_attn.mixfp4_attn_decode_paged(
            q, kp, ks, vp, vs, bt, ln, window=w, softcap=50.0,
            interpret=False),
        _sds(one_chip, (b, H, DH), jnp.bfloat16),
        *_kv(one_chip, (pages, page)),
        _sds(one_chip, (b, s // page), jnp.int32),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (), jnp.int32))
