"""Compile the kernels and the table lookup for a described TPU v5e.

Nothing runs here: each test lowers a kernel at granite_3_2b's
attention widths (32 query heads, 8 kv heads, head_dim 64), or the
windowed paged kernel and the grouped expert kernel at
mellum2_12b's (32 query heads over 4 kv heads of 128, window 1024; 64
experts of width 896 over d_model 2304, top-8), for one chip of a v5e
topology and compiles it with the TPU compiler, which refuses what the
Pallas interpreter accepts — tiles not aligned to the hardware, more
VMEM than a kernel may use.  The HLO must hold the
Mosaic kernel (`tpu_custom_call`), not the interpreter's loops.  The
MLP gate's table lookup, at granite_3_2b's chunk-step width, must
compile to no gather for the TPU and keep its gather on the CPU.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.intmath import apply_lut
from repro.kernels.moe_experts import moe_experts
from repro.kernels.paged_attention import (
    TPU_PAGE_ALIGN,
    paged_attention_pallas,
)

H, K, HD = 32, 8, 64
PS = TPU_PAGE_ALIGN


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(one_chip, *, B, S, pps, n_pages, packed, n_kv=K, hd=HD,
             window=None):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hd_store = hd // 2 if packed else hd
    pool = sds((n_pages + 1, n_kv, hd_store, PS), jnp.int8)
    args = [
        sds((B, H, S, hd), jnp.int8),
        pool,
        pool,
        sds((B, pps), jnp.int32),
        sds((B,), jnp.int32),
        sds((), jnp.float32),
    ]
    if packed:
        args += [sds((6, K), jnp.int32)] * 2

    def step(q, k, v, table, pos, scale, *rq):
        kw = dict(k_rq=rq[0], v_rq=rq[1]) if packed else {}
        return paged_attention_pallas(
            q, k, v, table, pos, score_scale=scale, group=H // n_kv,
            window=window, **kw
        )

    return jax.jit(step).lower(*args).compile()


@pytest.mark.parametrize(
    "B,S,pps,n_pages,packed",
    [
        pytest.param(8, 1, 9, 72, False, id="decode"),
        pytest.param(8, 128, 9, 72, False, id="prefill_chunk"),
        pytest.param(8, 128, 9, 72, True, id="int4_packed"),
        # 2048 pages x 128 positions per layer: the ~10 GB arena the
        # full-depth model holds on one chip; pools stay in HBM
        pytest.param(8, 1, 64, 2048, False, id="deployment_pool"),
    ],
)
def test_paged_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, B, S, pps, n_pages, packed
):
    compiled = _compile(
        one_chip, B=B, S=S, pps=pps, n_pages=n_pages, packed=packed
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * (n_pages + 1) * K * HD * PS // (2 if packed else 1)
    assert mem.argument_size_in_bytes >= pool_bytes


@pytest.mark.parametrize("S", [1, 256], ids=["decode", "prefill_chunk"])
def test_windowed_paged_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, S
):
    compiled = _compile(one_chip, B=32, S=S, pps=32, n_pages=1024,
                        packed=False, n_kv=4, hd=128, window=1024)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [32, 32 * 256], ids=["decode", "chunk"])
def test_moe_experts_compiles_for_v5e(one_chip, no_persistent_cache, T):
    E, d, f, top_k = 64, 2304, 896, 8

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rqt(*shape):
        return dict({n: sds(shape) for n in ("m", "s0", "lo", "hi")},
                    d=sds(()), zp=sds(()))

    t = {"wg_q": sds((E, d, f), jnp.int8), "wu_q": sds((E, d, f), jnp.int8),
         "wd_q": sds((E, f, d), jnp.int8), "g_rqt": rqt(E, f),
         "u_rqt": rqt(E, f), "h_rqt": rqt(), "o_rqt": rqt(E, d),
         "g_lut": sds((256,), jnp.int8), "zp_g": sds(())}
    compiled = jax.jit(moe_experts).lower(
        sds((T, d), jnp.int8), sds((T, top_k)), t).compile()
    assert "tpu_custom_call" in compiled.as_text()


# 32 slots x a 128-wide chunk x granite_3_2b's d_ff: the SiLU gate's
# stored values in one chunk step
GATE = (32, 128, 8192)
GATHER = re.compile(r"\bgather\(")


def _lookup_hlo(**sharding):
    stored = jax.ShapeDtypeStruct(GATE, jnp.int8, **sharding)
    table = jax.ShapeDtypeStruct((256,), jnp.int8, **sharding)
    return jax.jit(apply_lut).lower(stored, table).compile().as_text()


def test_lut_lookup_compiles_without_gather_for_v5e(
    one_chip, no_persistent_cache
):
    hlo = _lookup_hlo(sharding=one_chip)
    assert "select" in hlo
    assert not GATHER.search(hlo)


def test_lut_lookup_keeps_gather_on_cpu():
    assert GATHER.search(_lookup_hlo())
