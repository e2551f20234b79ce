"""Pallas TPU kernel: RWKV6 (Finch) WKV chunked scan.

Grid (batch, head, time-chunk), chunk innermost; the (D x D) linear-attention
state is carried in VMEM scratch.  Per-channel data-dependent decays make the
intra-chunk term a 3-tensor (t, s, d) contraction.  Mosaic lowers no 3-D
einsum, so it is accumulated one channel at a time as (chunk x chunk)
tiles, each with its exact exponent difference (no factorisation that
could overflow under strong decay).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ssd_scan import cumsum_rows


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_scr, *, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    f32 = jnp.float32
    r = r_ref[0, 0].astype(f32)                  # (L, D)
    k = k_ref[0, 0].astype(f32)
    v = v_ref[0, 0].astype(f32)
    lw = lw_ref[0, 0].astype(f32)                # log-decay, <= 0
    u = u_ref[0].astype(f32)                     # (1, D)
    L, D = r.shape

    cl = cumsum_rows(lw)                         # inclusive cumsum (L, D)
    ecl = cl - lw                                # exclusive cumsum (L, D)
    # intra-chunk: att[t,s] = sum_d r[t,d] exp(ecl_t - cl_s) k[s,d],  s < t
    strict = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    clT, kT = cl.T, k.T                          # (D, L)
    att = jnp.zeros((L, L), f32)
    for d in range(D):
        expo = jnp.where(strict, ecl[:, d:d + 1] - clT[d:d + 1, :], -jnp.inf)
        att += (r[:, d:d + 1] * jnp.exp(expo)) * kT[d:d + 1, :]
    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)   # (t, D)
    # bonus for the current token
    bonus = ((r * u) * k).sum(axis=1, keepdims=True)       # (t, 1)
    y += bonus * v
    # inter-chunk: y += (r_t * exp(ecl_t)) @ state
    s = s_scr[...]
    y += jax.lax.dot_general(r * jnp.exp(ecl), s, (((1,), (0,)), ((), ())),
                             preferred_element_type=f32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state <- diag(exp(cl_L)) state + sum_s exp(cl_L - cl_s) k_s v_s^T
    last = cl[L - 1:, :]                         # (1, D)
    G = jax.lax.dot_general(k * jnp.exp(last - cl), v,
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=f32)   # (D, D)
    s_scr[...] = s * jnp.exp(last).T + G


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_scan(r, k, v, w, u, *, chunk=64, interpret=False):
    """r,k,v,w: (B,S,H,D); u: (H,D) -> (B,S,H,D).

    Heads move ahead of time so each block's last two dims are a
    (chunk, D) tile."""
    B, S, H, D = r.shape
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-12, 1.0))
    heads_first = [jnp.moveaxis(a, 2, 1) for a in (r, k, v, lw)]
    grid = (B, H, nc)
    spec = pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0))
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, D), lambda b, h, c: (h, 0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), r.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(*heads_first, u[:, None, :])
    return jnp.moveaxis(y, 1, 2)
