"""SSD intra-chunk Pallas kernel (Mamba2 mixer hot spot).

Computes, for one chunk of length L per (batch, head):
    y_intra[i] = sum_{j<=i} (C_i·B_j) exp(cum_i - cum_j) dt_j x_j
    state_out  = sum_j exp(cum_L - cum_j) dt_j B_j (x)_j        (hd, ds)
    decay_out  = exp(cum_L)                                     scalar
so the host-level lax.scan only carries the (hd, ds) state recurrence.
Grid (B, H); the whole (L, ·) working set for one head sits in VMEM:
L=256, hd=64, ds<=128 -> ~0.5 MB.  The three L x L / L x hd contractions
run on the MXU; cumsum/exp are VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import on_backend

def _kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, st_ref, dec_ref, *, l):
    x = x_ref[0, :, 0].astype(jnp.float32)  # (L, hd)
    bm = b_ref[0, :, 0].astype(jnp.float32)  # (L, ds)
    cm = c_ref[0, :, 0].astype(jnp.float32)  # (L, ds)
    dt = dt_ref[0].astype(jnp.float32)  # (L, 1)
    a = a_ref[...].astype(jnp.float32)  # (1,)

    da = dt * a  # (L,1), <= 0
    cum = jnp.cumsum(da, axis=0)  # (L,1)
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L,L) C_i·B_j
    decay_arg = cum - cum[:, 0][None, :]  # cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.exp(jnp.where(ii >= jj, decay_arg, -1e30))
    scores = cb * decay * dt[:, 0][None, :]  # (L,L)
    y_ref[0, :, 0] = jax.lax.dot(
        scores, x, preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)

    wgt = jnp.exp(cum[-1, 0] - cum) * dt  # (L,1)
    st_ref[0, 0] = jax.lax.dot_general(
        x, bm * wgt, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(st_ref.dtype)  # (hd, ds)
    dec_ref[0, 0] = jnp.exp(cum[-1, 0]).astype(dec_ref.dtype)


def ssd_chunk_pallas(x, b, c, dt, a):
    """One-chunk SSD terms per (batch, head).

    x: (B, L, H, hd); b/c: (B, L, H, ds) (groups pre-broadcast);
    dt: (B, L, H) f32 post-softplus; a: (H,) f32 negative.
    Returns: y_intra (B, L, H, hd) f32, state (B, H, hd, ds) f32,
             chunk_decay (B, H) f32.
    """
    bsz, l, h, hd = x.shape
    ds = b.shape[-1]
    grid = (bsz, h)
    def build(interpret):
        return pl.pallas_call(
            functools.partial(_kernel, l=l),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, l, 1, hd), lambda bi, hi: (bi, 0, hi, 0)),
                pl.BlockSpec((1, l, 1, ds), lambda bi, hi: (bi, 0, hi, 0)),
                pl.BlockSpec((1, l, 1, ds), lambda bi, hi: (bi, 0, hi, 0)),
                pl.BlockSpec((1, l, 1), lambda bi, hi: (bi, 0, hi)),
                pl.BlockSpec((1,), lambda bi, hi: (hi,)),
            ],
            out_specs=[
                pl.BlockSpec((1, l, 1, hd), lambda bi, hi: (bi, 0, hi, 0)),
                pl.BlockSpec((1, 1, hd, ds), lambda bi, hi: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1), lambda bi, hi: (bi, hi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bsz, l, h, hd), jnp.float32),
                jax.ShapeDtypeStruct((bsz, h, hd, ds), jnp.float32),
                jax.ShapeDtypeStruct((bsz, h), jnp.float32),
            ],
            interpret=interpret,
        )

    y, st, dec = on_backend(build)(x, b, c, dt, a)
    return y, st, dec
