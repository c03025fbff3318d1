"""Pallas kernel: batched Eq. (10) subset-DP table build.

The exhaustive table builders evaluate every one of the 2^n cache subsets
for B independent rho rows (B = cells x versions x patterns on a sweep
grid).  The NumPy twin (``repro.core.batched._subset_dp``) walks a serial
``for m in range(1, 2^n)`` highest-set-bit recurrence — the one serial
loop left in the fast engine's table layer.  This kernel replaces its
row-dependent half (the [B, 2^n] exclusion-product matrix) with n masked
multiply sweeps over the 2^n subset lanes (see ``ref.py`` for why that is
bit-exact), tiled over B row blocks the way ``kernels/bloom/bloom.py``
tiles key blocks.  The row-independent cost sums ([2^n], adds only) and
the final ``cost + prod`` happen OUTSIDE the kernel — the final add must
not share a jitted computation with the multiplies, or XLA contracts the
pair into an FMA and the last ulp drifts off the oracle (``ref.py``
documents the contraction hazard).

Grid: (row_blocks,).  Block shapes (each meets the TPU's (8, 128)
tiling: a multiple of it or the whole array dim):
  mp    [1]               (SMEM scalar: the miss penalty — an input, not a
                           static, so one compilation serves a whole
                           penalty sweep)
  rhos  [RB, n]           (one row block)
  out   [RB, 2^n]         (subset products, M included)

The kernel runs in the dtype of ``rhos``.  TPUs have no float64: Mosaic
refuses X64 element types, so compiled mode takes float32 only and a
float64 call with ``interpret=False`` raises before reaching the
compiler.  The fast engine's float64 exactness contract is therefore met
in interpret mode (``default_interpret()`` picks it off-TPU) or by the
``"jax"``/``"numpy"`` backends of ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: elements (RB * 2^n) per output block: bounds VMEM/working-set per tile
DEFAULT_BLOCK_ELEMS = 1 << 16
MAX_ROW_BLOCK = 256


def _subsetdp_kernel(mp_ref, rhos_ref, out_ref, *, n: int):
    k = 1 << n
    rhos = rhos_ref[...]                                        # [RB, n]
    rb = rhos.shape[0]
    dtype = rhos.dtype
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)      # subset ids
    prod = jnp.full((rb, k), mp_ref[0], dtype)
    one = jnp.asarray(1.0, dtype)
    for j in range(n):      # n is small and static: unrolled, ascending j
        bit = ((lanes >> j) & 1) == 1
        prod = prod * jnp.where(bit, rhos[:, j][:, None], one)
    out_ref[...] = prod


def default_interpret() -> bool:
    """Compiled only on TPU; interpret mode everywhere else.  Pass
    ``interpret=False`` to override."""
    return jax.default_backend() != "tpu"


def default_row_block(n: int) -> int:
    """Rows per tile, scaled down with 2^n so a tile's output block stays
    near ``DEFAULT_BLOCK_ELEMS`` elements."""
    return max(1, min(MAX_ROW_BLOCK, DEFAULT_BLOCK_ELEMS >> n))


@functools.partial(jax.jit, static_argnames=("n", "row_block", "interpret"))
def _subset_prod_jit(mp, rhos, *, n: int, row_block: int, interpret: bool):
    b = rhos.shape[0]
    assert b % row_block == 0, (b, row_block)
    if not interpret and rhos.dtype == jnp.float64:
        raise TypeError(
            "subset-DP kernel: float64 cannot be compiled for a TPU — the "
            "chip has no float64 (Mosaic rejects X64 element types); pass "
            "float32 rows or run in interpret mode")
    k = 1 << n
    kernel = functools.partial(_subsetdp_kernel, n=n)
    return pl.pallas_call(
        kernel,
        grid=(b // row_block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # miss penalty
            pl.BlockSpec((row_block, n), lambda i: (i, 0)),     # rho block
        ],
        out_specs=pl.BlockSpec((row_block, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), rhos.dtype),
        interpret=interpret,
    )(mp, rhos)


def subset_prod_pallas(rhos, miss_penalty, *, row_block: int = None,
                       interpret: bool = None):
    """rhos: [B, n] (B % row_block == 0 — ``ops.subset_dp`` pads);
    miss_penalty: scalar or [1].  Returns the [B, 2^n] subset exclusion
    products (M included) in ``rhos.dtype``; add the per-subset cost sums
    outside the jitted computation to obtain Eq. (10) values.

    ``interpret=None`` (the default) auto-selects from the JAX backend:
    compiled on TPU, interpret mode elsewhere.
    """
    rhos = jnp.asarray(rhos)
    n = rhos.shape[1]
    if interpret is None:
        interpret = default_interpret()
    if row_block is None:
        row_block = default_row_block(n)
    mp = jnp.asarray(miss_penalty, rhos.dtype).reshape(1)
    return _subset_prod_jit(mp, rhos, n=n, row_block=row_block,
                            interpret=bool(interpret))
