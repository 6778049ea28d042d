"""Mamba selective-scan Pallas TPU kernel.

Tiling: grid (B, di/bd, S/bc) with the sequence-chunk axis innermost; the
SSM state, held transposed as (ds, bd), lives in VMEM scratch and is
carried across chunks. Within a chunk the recurrence is stepped with a
fori_loop over tiles of ROWS time steps (unrolled within a tile), while
the chunk's (bc, bd) blocks stream HBM<->VMEM once — the memory-
bound structure Mamba prescribes (state never leaves SRAM/VMEM), re-blocked
for TPU lanes: d_inner is tiled at 128 lanes, d_state (16) rides the
sublane dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# rows loaded per loop trip: one packed bf16 sublane tile (16 rows), a
# multiple of the f32 tile (8) -- Mosaic loads only whole tiles at
# dynamic offsets
ROWS = 16


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, y_ref, h_scr, *,
                bc: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    at = at_ref[...].astype(jnp.float32)    # (ds, bd)
    d = d_ref[...].astype(jnp.float32)      # (1, bd)

    def tile(g, h):                         # h: (ds, bd)
        rows = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        x = x_ref[0, rows, :].astype(jnp.float32)        # (ROWS, bd)
        dt = dt_ref[0, rows, :].astype(jnp.float32)      # (ROWS, bd)
        b_t = b_ref[0, rows, :].astype(jnp.float32).T    # (ds, ROWS)
        c_t = c_ref[0, rows, :].astype(jnp.float32).T    # (ds, ROWS)
        ys = []
        for r in range(ROWS):
            x_r, dt_r = x[r:r + 1], dt[r:r + 1]          # (1, bd)
            h = jnp.exp(dt_r * at) * h + b_t[:, r:r + 1] * (dt_r * x_r)
            ys.append(jnp.sum(h * c_t[:, r:r + 1], axis=0, keepdims=True))
        y = jnp.concatenate(ys, axis=0) + d * x
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, bc // ROWS, tile, h_scr[...])


@functools.partial(jax.jit, static_argnames=("bd", "bc", "interpret"))
def ssm_scan(x, dt, b_t, c_t, a, d, *, bd: int = 128, bc: int = 256,
             interpret: bool = False):
    """x, dt: (B,S,di); b_t, c_t: (B,S,ds); a: (di,ds); d: (di,)."""
    bsz, s, di = x.shape
    ds = a.shape[1]
    bd = min(bd, di)
    bc = min(bc, s)
    assert di % bd == 0 and s % bc == 0 and bc % ROWS == 0
    nd, nc = di // bd, s // bc

    kernel = functools.partial(_ssm_kernel, bc=bc)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nd, nc),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda b, i, j: (b, j, i)),   # x
            pl.BlockSpec((1, bc, bd), lambda b, i, j: (b, j, i)),   # dt
            pl.BlockSpec((1, bc, ds), lambda b, i, j: (b, j, 0)),   # B
            pl.BlockSpec((1, bc, ds), lambda b, i, j: (b, j, 0)),   # C
            pl.BlockSpec((ds, bd), lambda b, i, j: (0, i)),         # A^T
            pl.BlockSpec((1, bd), lambda b, i, j: (0, i)),          # D
        ],
        out_specs=pl.BlockSpec((1, bc, bd), lambda b, i, j: (b, j, i)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, di), x.dtype),
        scratch_shapes=[pltpu.VMEM((ds, bd), jnp.float32)],
        interpret=interpret,
    )(x, dt, b_t, c_t, a.T, d.reshape(1, di))
