"""Pallas TPU kernel: block-ELL SpMM with FUSED ABFT checksum epilogue.

The sparse analogue of ``kernels/matmul_abft``: H_out = S @ X where S is a
padded block-ELL adjacency (see ``layout.py``).  The grid walks
(row-stripe, ell-slot); the column-block index table rides as a
scalar-prefetch operand so each X tile's DMA address is known before the
body runs (``pltpu.PrefetchScalarGridSpec``).  ELL padding tiles alias
column-block 0 with zero values — they add nothing, so no masking.

Checksum epilogue, same trick as matmul_abft: the operands stay pristine
(no physically augmented rows/columns to break 128-lane tiling) and the
check quantities accumulate in VMEM scratch during the same HBM pass:

  outputs: out  = S @ X                 [M, G]
           stripe_sums[i] = Σ out_stripe  (actual checksum — final reduce
                                           is O(M/bm), done by ops.py)
           extra = S @ x_r             [M, 1]  (the carried eq.-5 column:
                    x_r = X e for a standalone check, or H w_r threaded
                    from the combination matmul for the full eq.-4 chain)

The G (output-feature) axis is not tiled: GCN widths (16–186 in the paper)
fit one lane block after ops.py pads them, which keeps the grid 2-D and the
extra column accumulating on every step — there is no ni==0 sweep guard to
get wrong.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def f32_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """MXU matmul at full f32 precision.  Mosaic's default runs an f32 dot
    as one bf16 pass, which on a TPU v5e left full-Cora logits 9e-5 (0.3 %
    of the largest logit) off a float32 reference."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _make_kernel(inject: Optional[Tuple[int, int, float]]):
    def _kernel(cols_ref, s_ref, x_ref, xr_ref, out_ref, sums_ref, extra_ref,
                acc_ref, ex_ref):
        j = pl.program_id(1)
        nj = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            ex_ref[...] = jnp.zeros_like(ex_ref)

        s = s_ref[0, 0]
        acc_ref[...] += f32_dot(s, x_ref[...])
        ex_ref[...] += f32_dot(s, xr_ref[...])

        if inject is not None:
            # same accumulator-upset hook as the fused kernel: perturbs one
            # element mid-sweep so the two-pass path's detection + surgical
            # repair can be exercised end to end
            ii, jj, delta = inject

            @pl.when((pl.program_id(0) == ii) & (j == jj))
            def _inject():
                acc_ref[0:1, 0:1] += jnp.float32(delta)

        @pl.when(j == nj - 1)
        def _epilogue():
            acc = acc_ref[...]
            out_ref[...] = acc.astype(out_ref.dtype)
            sums_ref[...] = jnp.full(sums_ref.shape, jnp.sum(acc))
            extra_ref[...] = ex_ref[...]

    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret", "inject"))
def spmm_abft_kernel(block_cols: jax.Array, values: jax.Array, x: jax.Array,
                     xr: jax.Array, *, interpret: bool = False,
                     inject: Optional[Tuple[int, int, float]] = None):
    """block_cols: [nbm, width] i32; values: [nbm, width, bm, bk];
    x: [K, G]; xr: [K, 1].  K and G must be padded by the caller (ops.py)
    to bk / lane multiples and to cover max(block_cols)+1 stripes.
    ``inject=(stripe, slot, delta)`` perturbs one accumulator element
    mid-sweep (CI fault hook).
    Returns (out [nbm*bm, G], stripe_sums [nbm, 1], extra [nbm*bm, 1]).

    The stripe sums leave the kernel as [nbm, 1, 1] with (1, 1, 1) blocks:
    Mosaic requires a block's last two dims to be (8, 128)-divisible or
    to span the array, which a (1, 1) block over [nbm, 1] does not."""
    nbm, width, bm, bk = values.shape
    k, g = x.shape
    assert k % bk == 0 and xr.shape == (k, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbm, width),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda i, j, cols: (i, j, 0, 0)),
            pl.BlockSpec((bk, g), lambda i, j, cols: (cols[i, j], 0)),
            pl.BlockSpec((bk, 1), lambda i, j, cols: (cols[i, j], 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, g), lambda i, j, cols: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, cols: (i, 0, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, cols: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, g), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
        ],
    )
    out, sums, extra = pl.pallas_call(
        _make_kernel(inject),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nbm * bm, g), x.dtype),
            jax.ShapeDtypeStruct((nbm, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nbm * bm, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_cols, values, x, xr)
    return out, sums.reshape(nbm, 1), extra
