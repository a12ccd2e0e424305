"""Main-path Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: each case lowers a kernel at a published model's width
for one chip of a ``v5e:2x2`` topology and compiles it with the TPU
compiler, so a tiling or VMEM refusal shows up here instead of on the
chip.  Each compiled program must hold the Mosaic kernel
(``tpu_custom_call``), i.e. no case silently fell back to plain XLA.
The olmo-1b paged programs are compiled whole, pool donated, to pin
that the stacked KV pool is updated in place: no pool-sized copy and
almost no temporaries.  The topology is described inside a fixture,
never at import time: only one process at a time may load the TPU
compiler's library.
"""
import functools
import math
import re

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

# olmo-1b as served: 16 layers, 512 pages of 64 tokens, 16 kv heads of
# 128, decode batch 32, block tables 32 pages wide, chunks of 256 tokens
OLMO_POOL = (16, 512, 16, 64, 128)
OLMO_PROGRAMS = ("decode", "prefill_tail", "prefill")


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


# (q heads, kv heads, head dim, page dtype, v_dim): olmo-1b MHA,
# codeqwen1.5-7b MHA (32 kv heads of 128, the widest page block of any
# configuration: 1 MiB of K and V a grid step), a GQA group of 4, int8
# pages, and minicpm3's absorbed-MLA latent pages (one kv head of
# kv_lora + d_rope = 288, v = the leading 256 features)
PAGED_CASES = {
    "olmo1b_bf16": (16, 16, 128, BF16, None),
    "codeqwen15_7b": (32, 32, 128, BF16, None),
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


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_stacked_paged_attention_compiles_for_v5e(one_chip, case):
    """The same kernels reading one layer of a 16-layer stacked pool,
    the layer index a traced scalar-prefetch operand."""
    h, kv, hd, dt, v_dim = PAGED_CASES[case]
    layers = OLMO_POOL[0]
    shapes = [((B, h, hd), BF16), ((layers, PAGES, kv, PS, hd), dt),
              ((layers, PAGES, kv, PS, hd), dt), ((B, M), I32), ((B,), I32),
              ((), I32)]
    if dt == I8:
        shapes += [((layers, PAGES, kv, 1, PS), BF16)] * 2

    def fn(q, k, v, bt, ln, layer, *scales):
        return _paged(q, k, v, bt, ln, *scales, v_dim=v_dim, layer=layer)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def test_stacked_paged_attention_compiles_at_served_shape(one_chip):
    """olmo-1b's kernel at the shape the decode step serves it: batch
    32, block tables 32 pages wide, the whole 16-layer stacked pool."""
    q_heads, hd = 16, OLMO_POOL[-1]
    b, m = 32, 32
    shapes = [((b, q_heads, hd), BF16), (OLMO_POOL, BF16), (OLMO_POOL, BF16),
              ((b, m), I32), ((b,), I32), ((), I32)]

    def fn(q, k, v, bt, ln, layer):
        return _paged(q, k, v, bt, ln, layer=layer)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def test_mux_score_compiles_for_v5e(one_chip):
    shapes = [((8, 512), F32), ((3, 512), F32), ((3,), F32)]
    assert "tpu_custom_call" in _compiled_text(mux_score, shapes, one_chip)


def test_flash_attention_compiles_for_v5e(one_chip):
    shapes = [((1, 2048, 16, 128), BF16)] * 3
    assert "tpu_custom_call" in _compiled_text(flash_attention, shapes,
                                               one_chip)


# "%name = <shape> opcode(operands...), attributes"; a tuple shape holds
# spaces, but no " word(" — only the opcode is a word right before "("
_INSTR = re.compile(r"^\s*(ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\((.*)$")


@pytest.fixture(scope="module")
def olmo_compiled(one_chip):
    """The paged decode step, tail prefill and whole-prompt prefill of
    olmo-1b at full width, compiled as the engine jits them (pool
    donated) with the Pallas path on, as on the chip."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import transformer as tf

    cfg = get_config("olmo-1b")

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    params = jax.tree.map(place, tf.abstract_params(cfg, "bfloat16"))
    caches = jax.tree.map(place, tf.abstract_caches(
        cfg, 0, 0, BF16, num_pages=OLMO_POOL[1], page_size=OLMO_POOL[3]))
    b, m, s = 32, 32, 256

    def decode(p, token, caches, bt, pos):
        return tf.decode_step(p, cfg, token, caches, pos, block_tables=bt)

    def prefill_tail(p, tokens, caches, bt, last, q_offset, insert_from):
        return tf.prefill_paged(p, cfg, tokens, caches, bt, last,
                                q_offset=q_offset, insert_from=insert_from)

    def prefill(p, tokens, caches, bt, last):
        return tf.prefill_paged(p, cfg, tokens, caches, bt, last)

    args = {
        "decode": (decode, (params, i32(b, 1), caches, i32(b, m), i32(b))),
        "prefill_tail": (prefill_tail, (params, i32(1, s), caches,
                                        i32(1, m), i32(), i32(), i32())),
        "prefill": (prefill, (params, i32(1, s), caches, i32(1, m), i32())),
    }
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_FORCE", True)
        mp.setattr(ops, "_interpret", lambda: False)
        for name, (fn, a) in args.items():
            out[name] = jax.jit(fn, donate_argnums=(2,)).lower(*a).compile()
    return out


def _pool_sized_ops(hlo: str):
    """(name, opcode) of every bf16 instruction with as many elements as
    the stacked pool or one layer of it, in any shape of rows of
    head_dim (so not the stacked (16, 2048, 2048) projections), that is
    not a parameter, a loop tuple element, a bitcast or an in-place
    scatter (a scatter, or a fusion whose root is one)."""
    sizes = {math.prod(OLMO_POOL), math.prod(OLMO_POOL[1:])}
    roots, instrs, comp = {}, [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        ins = _INSTR.match(line)
        if head:
            comp = head.group(1)
        elif ins:
            root, name, shape, op, rest = ins.groups()
            if root:
                roots[comp] = op
            instrs.append((name, shape, op, rest))
    bad = []
    for name, shape, op, rest in instrs:
        dims = re.match(r"bf16\[([\d,]+)\]", shape)
        dims = [int(d) for d in dims.group(1).split(",")] if dims else [0]
        if math.prod(dims) not in sizes or dims[-1] != OLMO_POOL[-1]:
            continue
        if op in ("parameter", "get-tuple-element", "bitcast", "scatter"):
            continue
        called = re.search(r"calls=%([^,\s]+)", rest)
        if op == "fusion" and called and roots.get(called.group(1)) == \
                "scatter":
            continue
        bad.append((name, op))
    return bad


@pytest.mark.parametrize("program", OLMO_PROGRAMS)
def test_olmo_paged_program_temporaries_are_small(olmo_compiled, program):
    """The pool (4 GiB) is updated in place: under 256 MiB of
    temporaries, where slicing each layer out of the stacked pool and
    restacking it took 4.8-5.4 GB."""
    temp = olmo_compiled[program].memory_analysis().temp_size_in_bytes
    assert temp < 256 * 2**20, temp


@pytest.mark.parametrize("program", OLMO_PROGRAMS)
def test_olmo_paged_program_copies_no_pool(olmo_compiled, program):
    """No operation but the in-place scatters and the loop's own tuple
    and parameters produces the stacked pool or a layer slice of it."""
    assert _pool_sized_ops(olmo_compiled[program].as_text()) == []


def test_olmo_paged_decode_holds_kernel(olmo_compiled):
    assert "tpu_custom_call" in olmo_compiled["decode"].as_text()
