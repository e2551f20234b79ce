"""Pallas TPU kernel: Mamba2 SSD chunked scan (zamba2's SSM core).

Grid is (batch, head, time-chunk) with the chunk axis innermost
(sequential); the (P x N) recurrent state lives in VMEM scratch across chunk
steps.  Each step computes the intra-chunk quadratic term on the MXU
(chunk x chunk interaction matrix) plus the inter-chunk contribution from
the carried state — the state-space-dual algorithm, tiled so the working
set (chunk x P inputs, chunk x N B/C blocks, P x N state, chunk x chunk
decay) fits VMEM with MXU-aligned dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def cumsum_rows(a):
    """Inclusive cumsum down the rows of a 2-D block, as a lower-triangular
    matmul (Mosaic has no cumsum lowering)."""
    n = a.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) >=
           jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(jnp.float32)
    return jax.lax.dot_general(tri, a, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, dt_ref, a_ref, B_ref, C_ref, y_ref, h_scr, *, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    f32 = jnp.float32
    x = x_ref[0, 0].astype(f32)                     # (L, P)
    dt = dt_ref[0, 0].astype(f32)                   # (L, 1)
    a = a_ref[0, 0].astype(f32)                     # (L, 1): A * dt
    Bm = B_ref[0].astype(f32)                       # (L, N)
    Cm = C_ref[0].astype(f32)                       # (L, N)

    acs = cumsum_rows(a)                           # (L, 1)
    # intra-chunk decay matrix, lower-triangular in (t, s)
    diff = acs - acs.T
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)          # (t, s)
    w = cb * decay * dt.T
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=f32)     # (t, P)

    # inter-chunk: y_inter[t] = exp(acs_t) * C_t . h_in  (h: (P, N))
    h = h_scr[...]
    ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)          # (t, P)
    y_ref[0, 0] = (y_intra + jnp.exp(acs) * ch).astype(y_ref.dtype)

    # state update: h <- exp(acs_L) h + sum_s exp(acs_L - acs_s) dt_s x_s B_s^T
    last = acs[chunk - 1:, :]                       # (1, 1)
    tail = jnp.exp(last - acs) * dt                 # (L, 1)
    G = jax.lax.dot_general(x * tail, Bm, (((0,), (0,)), ((), ())),
                            preferred_element_type=f32)           # (P, N)
    # exp(acs_L) as an (1, N) row: Mosaic cannot broadcast a (1, 1) value
    # over sublanes and lanes at once, so the row is a ones-matmul of a
    total = jax.lax.dot_general(a, jnp.ones((chunk, h.shape[1]), f32),
                                (((0,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=f32)       # (1, N)
    h_scr[...] = h * jnp.exp(total) + G


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B_, C, *, chunk=128, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); B_,C: (B,S,N) -> y: (B,S,H,P).

    Heads move ahead of time so each block's last two dims are a
    (chunk, P) or (chunk, 1) tile; A is folded into dt outside."""
    Bb, S, H, P = x.shape
    N = B_.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    grid = (Bb, H, nc)
    xt = jnp.moveaxis(x, 2, 1)                                  # (B,H,S,P)
    dtt = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)[..., None]  # (B,H,S,1)
    at = dtt * A.astype(jnp.float32)[None, :, None, None]
    col = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0))
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            col, col,
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, at, B_, C)
    return jnp.moveaxis(y, 1, 2)
