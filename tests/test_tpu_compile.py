"""Main-path Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: each case lowers a kernel at a published model's width
for one chip of a ``v5e:2x2`` topology and compiles it with the TPU
compiler, so a tiling or VMEM refusal shows up here instead of on the
chip.  Each compiled program must hold the Mosaic kernel
(``tpu_custom_call``), i.e. no case silently fell back to plain XLA.
The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU compiler's library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.mux_score import mux_score
from repro.kernels.paged_attention import decode_prefetch, paged_attention

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32

# olmo-1b decode: B=8, 16/16 heads of 128, page 64, 512 pages, 32 pages/row
B, PS, PAGES, M = 8, 64, 512, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler library: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged(q, k, v, bt, ln, *scales, **kw):
    ks, vs = scales if scales else (None, None)
    return paged_attention(q, k, v, bt, ln, k_scales=ks, v_scales=vs,
                           prefetch=decode_prefetch(bt, ln), **kw)


# (q heads, kv heads, head dim, page dtype, v_dim): olmo-1b MHA, a GQA
# group of 4, int8 pages, and minicpm3's absorbed-MLA latent pages
# (one kv head of kv_lora + d_rope = 288, v = the leading 256 features)
PAGED_CASES = {
    "olmo1b_bf16": (16, 16, 128, BF16, None),
    "gqa_g4": (32, 8, 128, BF16, None),
    "int8_pages": (16, 16, 128, I8, None),
    "mla_vdim": (40, 1, 288, BF16, 256),
}


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_attention_compiles_for_v5e(one_chip, case):
    h, kv, hd, dt, v_dim = PAGED_CASES[case]
    shapes = [((B, h, hd), BF16), ((PAGES, kv, PS, hd), dt),
              ((PAGES, kv, PS, hd), dt), ((B, M), I32), ((B,), I32)]
    if dt == I8:
        shapes += [((PAGES, kv, 1, PS), BF16)] * 2
    fn = functools.partial(_paged, v_dim=v_dim)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def test_mux_score_compiles_for_v5e(one_chip):
    shapes = [((8, 512), F32), ((3, 512), F32), ((3,), F32)]
    assert "tpu_custom_call" in _compiled_text(mux_score, shapes, one_chip)


def test_flash_attention_compiles_for_v5e(one_chip):
    shapes = [((1, 2048, 16, 128), BF16)] * 3
    assert "tpu_custom_call" in _compiled_text(flash_attention, shapes,
                                               one_chip)
