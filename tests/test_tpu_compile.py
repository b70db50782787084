"""The serve path's attention kernels compile for a TPU v5e at gemma2-2b's
published widths (Hq 8, Hkv 4, head_dim 256, page_size 16), and the paged
kernel at the shapes the benchmark's phi4-mini cell serves.

Ahead-of-time compiles against a *described* ``v5e:2x2`` topology with
``interpret=False``: nothing runs, but the chip's compiler checks what the
interpreter cannot -- block shapes against the (8, 128) tiling, Mosaic
layouts, VMEM use.  The topology (which loads the TPU compiler library) is
described inside a fixture, never at import, and skipped from there where
it cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import flash_attention, paged_prefill_attention

HQ, HKV, HD, PS = 8, 4, 256, 16           # gemma2-2b attention widths
WINDOW, CAP = 4096, 50.0
SLOTS, BLOCKS = 8, 64                     # 8 sequences x 1024 positions
PAGES = SLOTS * BLOCKS + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache(topo):
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the kernel, not an emulation


def test_flash_prefill_compiles(one_chip, no_persistent_cache):
    B, S = 2, 512
    _compile(
        lambda q, k, v, qp, kp: flash_attention(
            q, k, v, q_pos=qp, kv_pos=kp, window=WINDOW, attn_cap=CAP,
            interpret=False),
        [((B, S, HQ, HD), jnp.bfloat16), ((B, S, HKV, HD), jnp.bfloat16),
         ((B, S, HKV, HD), jnp.bfloat16), ((B, S), jnp.int32),
         ((B, S), jnp.int32)], one_chip)


@pytest.mark.parametrize("k,kv_bits", [(1, None), (16, None), (1, 8),
                                       (16, 8)],
                         ids=["decode", "chunk", "decode-int8",
                              "chunk-int8"])
def test_paged_attention_compiles(k, kv_bits, one_chip, no_persistent_cache):
    """k=1 is the decode step, k=16 a prompt chunk / verify span; int8
    pools add the per-(head, slot) scale pages."""
    kv_dt = jnp.int8 if kv_bits == 8 else jnp.bfloat16
    shapes = [((SLOTS, k, HQ, HD), jnp.bfloat16),
              ((PAGES, HKV, PS, HD), kv_dt), ((PAGES, HKV, PS, HD), kv_dt),
              ((PAGES, PS), jnp.int32), ((SLOTS, BLOCKS), jnp.int32),
              ((SLOTS, k), jnp.int32)]
    if kv_bits == 8:
        shapes += [((PAGES, HKV, PS), jnp.float32)] * 2

    def fn(q, kp, vp, pos, bt, qp, *scales):
        ks, vs = scales or (None, None)
        return paged_prefill_attention(
            q, kp, vp, pos, bt, q_pos=qp, window=WINDOW, attn_cap=CAP,
            k_scale_pages=ks, v_scale_pages=vs, interpret=False)

    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("k", [256, 1], ids=["chunk", "decode"])
def test_paged_attention_compiles_at_phi4_mini_cell(k, one_chip,
                                                    no_persistent_cache):
    """phi4-mini's widths (Hq 24, Hkv 8, head_dim 128) at the prefill-long
    cell's shapes: 4 lanes x 514 blocks of 16 positions, int8 pools with
    scale pages, no window.  The 256-token chunk makes 768 q rows per KV
    head, the kernel's largest VMEM footprint in the benchmark."""
    B, hq, hkv, hd, ps, nb = 4, 24, 8, 128, 16, 514
    P = B * nb + 1
    shapes = [((B, k, hq, hd), jnp.bfloat16),
              ((P, hkv, ps, hd), jnp.int8), ((P, hkv, ps, hd), jnp.int8),
              ((P, ps), jnp.int32), ((B, nb), jnp.int32),
              ((B, k), jnp.int32),
              ((P, hkv, ps), jnp.float32), ((P, hkv, ps), jnp.float32)]

    def fn(q, kp, vp, pos, bt, qp, ks, vs):
        return paged_prefill_attention(
            q, kp, vp, pos, bt, q_pos=qp, k_scale_pages=ks,
            v_scale_pages=vs, interpret=False)

    _compile(fn, shapes, one_chip)
