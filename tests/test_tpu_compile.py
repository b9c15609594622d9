"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel against one chip of a described
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode accepts (block shapes off the (8, 128) tiling, 8-bit
shifts, VMEM overruns). The topology is described inside a fixture, never
at import, so every test worker collects the same tests and only the one
that runs this file loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels import ops
from repro.kernels.qconv1d import qconv1d_block_p
from repro.kernels.qmatmul import qmatmul_p
from repro.models.basecaller import model as bc


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("k", [9, 75])
def test_qconv1d_rubicall_window(sds, k):
    """RUBICALL's fused block at 344 channels over one serving window
    (default 1,024-sample core plus the halo on each side, after the
    stem's stride), four slots, bf16 activations."""
    cfg = get_config("rubicall")
    stride = bc.total_stride(cfg)
    frames = (-(-1024 // stride) * stride + 2 * bc.chunk_halo(cfg)) // stride
    C = cfg.channels[0]
    f32 = jnp.float32
    _compile(lambda *a: qconv1d_block_p(*a, interpret=False),
             sds((4, frames + k - 1, C), jnp.bfloat16), sds((k, C), jnp.int8),
             sds((C, C), jnp.int8), *[sds((1, C), f32)] * 4)


@pytest.mark.parametrize("bits", [8, 4])
def test_qmatmul_qwen_mlp(sds, bits):
    """Packed-weight matmul at qwen1.5-4b's MLP width (2560 -> 6912)."""
    K, N = 2560, 6912
    rows = K if bits == 8 else K // 2
    _compile(lambda x, w, s: qmatmul_p(x, w, s, bits=bits, interpret=False),
             sds((16, K), jnp.bfloat16), sds((rows, N), jnp.int8),
             sds((1, N), jnp.float32))


@pytest.mark.parametrize("C", [1, 16])
@pytest.mark.parametrize("cache", ["bf16", "int8", "float32"])
@pytest.mark.parametrize("hkv,group", [(20, 1), (8, 4)])
def test_paged_gqa(sds, C, cache, hkv, group):
    """Fused paged GQA over a heads-major arena at head_dim 128: decode
    (C == 1) and chunk prefill (C == 16); qwen1.5-4b's MHA (20 KV heads)
    and an 8-KV-head GQA; bf16 and int8 arenas, and float32 at full
    matmul precision (the precision of a float32 parity run)."""
    B, bl, T, hd = 4, 16, 8, 128
    nb = B * T
    qdt = jnp.float32 if cache == "float32" else jnp.bfloat16
    kdt = {"bf16": jnp.bfloat16, "int8": jnp.int8}.get(cache, jnp.float32)
    scale = sds((nb, hkv, bl), jnp.float32) if cache == "int8" else None
    i32 = jnp.int32
    precision = "highest" if cache == "float32" else None
    with jax.default_matmul_precision(precision):
        _compile(lambda q, k, v, pos, t, tbl, ks, vs: ops.decode_gqa(
                     q, k, v, pos, t, table=tbl, backend="pallas",
                     k_scale=ks, v_scale=vs, interpret=False),
                 sds((B, C, hkv * group, hd), qdt),
                 *[sds((nb, hkv, bl, hd), kdt)] * 2, sds((B, T * bl), i32),
                 sds((B, C), i32), sds((B, T), i32), scale, scale)


@pytest.mark.parametrize("C", [1, 4])
@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_paged_mla(sds, C, cache):
    """Fused paged absorbed-MLA at deepseek-v3's latent 512 + rope 64
    with its 128 heads: decode and chunk prefill, bf16 and int8."""
    B, bl, T, H, kvr, rope_d = 4, 16, 8, 128, 512, 64
    nb = B * T
    kdt = jnp.int8 if cache == "int8" else jnp.bfloat16
    scale = sds((nb, bl), jnp.float32) if cache == "int8" else None
    i32 = jnp.int32
    _compile(lambda qa, qr, c, kr, pos, t, tbl, cs, krs: ops.decode_mla(
                 qa, qr, c, kr, pos, t, scale=0.07, table=tbl,
                 backend="pallas", c_scale=cs, kr_scale=krs,
                 interpret=False),
             sds((B, C, H, kvr), jnp.bfloat16),
             sds((B, C, H, rope_d), jnp.bfloat16), sds((nb, bl, kvr), kdt),
             sds((nb, bl, rope_d), kdt), sds((B, T * bl), i32),
             sds((B, C), i32), sds((B, T), i32), scale, scale)
