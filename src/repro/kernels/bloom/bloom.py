"""Pallas TPU kernel: batched Bloom-filter probe.

HARDWARE ADAPTATION: a Bloom probe is a random gather — hostile to TPU
vector memory.  Instead of gathering, each probe extracts its 32-bit
filter word with a blocked iota-compare + select-reduce over the filter
row held in VMEM (regular, fully vectorised VPU work; no scatter/gather
unit needed).  Cost is O(k * m_words) compares per key — the right trade
below ~1M filter bits, where every filter row fits in VMEM and compares
are cheaper than an HBM-latency-bound gather chain.

The byte-packed bitmaps (``bits[n, m_bytes]`` uint8, see ``ref.py``) are
repacked outside the kernel into little-endian int32 words
(``words[n, m_bytes // 4]``), so bit ``i`` of a filter is bit ``i & 31``
of word ``i >> 5`` — a quarter of the compares of a byte layout.

Grid: (key_blocks,).  Every block shape meets the TPU's (8, 128) tiling
(a block dim is a multiple of it or the whole array dim):
  seeds  [n]            (SMEM, pre-mixed per-cache hash seeds)
  keys   [KB, 1]        (one key block, a key per sublane)
  words  [n, m_words]   (every filter, resident in VMEM)
  out    [n, KB]        (int32 indications, lane-dense: a key per lane)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bloom.ref import U, _mix32

DEFAULT_KEY_BLOCK = 256
BYTE_BLOCK = 2048
WORD_BLOCK = BYTE_BLOCK // 4
LANES = 128


def _probe_kernel(seeds_ref, keys_ref, words_ref, out_ref, *, k: int, m: int):
    n, mwords = words_ref.shape
    keys = keys_ref[...].astype(U)                           # [KB, 1]
    kb = keys.shape[0]
    h2 = _mix32(keys ^ U(0x85EBCA6B)) | U(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out = jnp.zeros((kb, LANES), jnp.int32)
    for j in range(n):       # n is small and static: one column per cache
        h1 = _mix32(keys ^ seeds_ref[j].astype(U))

        def probe(p, acc):
            idx = (h1 + p.astype(U) * h2) % U(m)
            word_idx = (idx >> U(5)).astype(jnp.int32)       # [KB, 1]
            bit = (idx & U(31)).astype(jnp.int32)
            word = jnp.zeros((kb, 1), jnp.int32)
            for c in range(mwords // WORD_BLOCK):
                lo = c * WORD_BLOCK
                lanes = lo + jax.lax.broadcasted_iota(
                    jnp.int32, (1, WORD_BLOCK), 1)
                chunk = words_ref[j:j + 1, lo:lo + WORD_BLOCK]  # [1, WB]
                sel = jnp.where(word_idx == lanes, chunk, 0)
                word = word + jnp.sum(sel, axis=1, keepdims=True)
            return acc & ((word >> bit) & 1)

        hit = jax.lax.fori_loop(0, k, probe, jnp.ones((kb, 1), jnp.int32))
        out = jnp.where(lane == j, hit, out)
    out_ref[...] = out.T[:n, :]


def default_interpret() -> bool:
    """Compiled only on TPU; interpret mode everywhere else — including
    GPU, deliberately: the kernel's blocked iota-compare/select-reduce
    design targets TPU VMEM (see module docstring) and is not expected to
    lower well elsewhere.  Pass ``interpret=False`` to override."""
    return jax.default_backend() != "tpu"


def _pack_words(bits):
    """[n, m_bytes] uint8 -> [n, m_bytes // 4] int32 little-endian words."""
    n, mbytes = bits.shape
    b = bits.astype(U).reshape(n, mbytes // 4, 4)
    w = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)
    return jax.lax.bitcast_convert_type(w, jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "key_block", "interpret"))
def _bloom_probe_jit(bits, keys, seeds, *, k: int, key_block: int,
                     interpret: bool):
    n, mbytes = bits.shape
    b = keys.shape[0]
    assert b % key_block == 0, (b, key_block)
    assert mbytes % BYTE_BLOCK == 0, mbytes
    if n > LANES:
        raise ValueError(f"bloom probe takes at most {LANES} caches, got {n}")
    m = mbytes * 8
    words = _pack_words(bits)
    # the ref's per-cache seed mix (seed * golden ratio mod 2^32), hoisted
    seed_mix = jax.lax.bitcast_convert_type(
        seeds.astype(U) * U(0x9E3779B9), jnp.int32)
    kernel = functools.partial(_probe_kernel, k=k, m=m)
    out = pl.pallas_call(
        kernel,
        grid=(b // key_block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # seeds
            pl.BlockSpec((key_block, 1), lambda i: (i, 0)),         # keys
            pl.BlockSpec((n, mbytes // 4), lambda i: (0, 0)),       # filters
        ],
        out_specs=pl.BlockSpec((n, key_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.int32),
        interpret=interpret,
    )(seed_mix, keys.astype(jnp.int32).reshape(b, 1), words)
    return out.T.astype(jnp.int8)


def bloom_probe_pallas(bits, keys, seeds, *, k: int,
                       key_block: int = DEFAULT_KEY_BLOCK,
                       interpret: bool = None):
    """bits: [n, m_bytes] uint8 (m_bytes % 2048 == 0); keys: [B] int32/uint32;
    seeds: [n] int32.  Returns [B, n] int8 indications.

    ``interpret=None`` (the default) auto-selects from the JAX backend:
    compiled on TPU, interpret mode elsewhere.
    """
    if interpret is None:
        interpret = default_interpret()
    return _bloom_probe_jit(bits, keys, seeds, k=k, key_block=key_block,
                            interpret=bool(interpret))
