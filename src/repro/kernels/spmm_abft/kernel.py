"""Pallas TPU kernel: block-ELL SpMM with FUSED ABFT checksum epilogue.

The sparse analogue of ``kernels/matmul_abft``: H_out = S @ X where S is a
padded block-ELL adjacency (see ``layout.py``).  The grid walks
(row-stripe, ell-slot); the column-block index table rides as a
scalar-prefetch operand so each X tile's DMA address is known before the
body runs (``pltpu.PrefetchScalarGridSpec``).  ELL padding tiles alias
column-block 0 with zero values — they add nothing, so no masking.

Checksum epilogue, same trick as matmul_abft: no physically augmented
rows of S; the eq.-5 column rides in X's lane padding instead.  The
wrapper lays X (logical width g) and the carried column x_r side by side
as one operand ``xa = [X | x_r | 0]`` of ``G = round_up(g + 1, block_g)``
lanes, so each stored tile is ONE MXU dot, ``S_tile @ xa_tile``, and lane
g of the accumulator is S·x_r on the same schedule as the output:

  outputs: out  = S @ xa                [M, G]  (lanes < g: S @ X)
           stripe_sums[i] = Σ_{lanes<g} out_stripe  (actual checksum —
                                           final reduce is O(M/bm), ops.py)
           extra = out[:, g]  = S @ x_r  [M, 1]  (the carried eq.-5 column:
                    x_r = X e for a standalone check, or H w_r threaded
                    from the combination matmul for the full eq.-4 chain)

The G axis is not tiled: GCN widths (3–186) plus the check lane fit one
or two lane blocks, which keeps the grid 2-D and the check column
accumulating on every step — there is no ni==0 sweep guard to get wrong.
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


def _make_kernel(g: int, inject: Optional[Tuple[int, int, float]]):
    def _kernel(cols_ref, s_ref, x_ref, out_ref, sums_ref, acc_ref):
        j = pl.program_id(1)
        nj = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += f32_dot(s_ref[0, 0], x_ref[...])

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
            # the actual checksum covers the output lanes only: the check
            # lane g and the zero padding are masked, not subtracted, so
            # the sum rounds as it would over S @ X alone
            lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
            sums_ref[...] = jnp.full(sums_ref.shape,
                                     jnp.sum(jnp.where(lane < g, acc, 0.0)))

    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret", "inject", "block_g"))
def spmm_abft_kernel(block_cols: jax.Array, values: jax.Array, x: jax.Array,
                     xr: jax.Array, *, interpret: bool = False,
                     inject: Optional[Tuple[int, int, float]] = None,
                     block_g: int = 128):
    """block_cols: [nbm, width] i32; values: [nbm, width, bm, bk];
    x: [K, g] at its logical width; xr: [K, 1].  K must be padded by the
    caller (ops.py) to a bk multiple covering max(block_cols)+1 stripes.
    ``block_g`` is the lane multiple g + 1 is rounded up to.
    ``inject=(stripe, slot, delta)`` perturbs one accumulator element
    mid-sweep (CI fault hook).
    Returns (out [nbm*bm, G], stripe_sums [nbm, 1], extra [nbm*bm, 1]);
    out's lanes >= g are the check lane and padding, for the caller to
    trim.

    The stripe sums leave the kernel as [nbm, 1, 1] with (1, 1, 1) blocks:
    Mosaic requires a block's last two dims to be (8, 128)-divisible or
    to span the array, which a (1, 1) block over [nbm, 1] does not."""
    nbm, width, bm, bk = values.shape
    k, g = x.shape
    assert k % bk == 0 and xr.shape == (k, 1)
    lanes = -(-(g + 1) // block_g) * block_g
    # x_r is f32 (ops.py), so a narrower X widens here and out below
    # narrows back: the check lane keeps f32 either way
    xa = jnp.concatenate(
        [x, xr, jnp.zeros((k, lanes - g - 1), xr.dtype)], axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbm, width),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda i, j, cols: (i, j, 0, 0)),
            pl.BlockSpec((bk, lanes), lambda i, j, cols: (cols[i, j], 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, lanes), lambda i, j, cols: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, cols: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, lanes), jnp.float32)],
    )
    out, sums = pl.pallas_call(
        _make_kernel(g, inject),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nbm * bm, lanes), xa.dtype),
            jax.ShapeDtypeStruct((nbm, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_cols, values, xa)
    return (out.astype(x.dtype), sums.reshape(nbm, 1),
            out[:, g:g + 1].astype(jnp.float32))
