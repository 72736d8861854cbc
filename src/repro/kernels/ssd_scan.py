"""Pallas TPU Mamba-2 SSD (state-space duality) chunked scan.

TPU mapping: grid = (batch, heads, chunks); the chunk axis is 'arbitrary'
(sequential) and the inter-chunk SSM state h (head_dim x state) lives in
VMEM scratch, carried across grid steps — the recurrence never round-trips
to HBM. Each step does the intra-chunk quadratic part on the MXU
(Q x Q score matrix, Q = chunk length) plus the state update/readout.

Validated against ref.ssd_ref with interpret=True on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
            h_ref, *, chunk: int, nchunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (Q, 1) -- blocked (Q,)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32)) # scalar in (1,)
    B = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)           # (Q, N)
    D = d_ref[0].astype(jnp.float32)

    dA = dt * a                                   # (Q, 1)
    cs = jnp.cumsum(dA, axis=0)                   # (Q, 1)
    xdt = x * dt                                  # (Q, P)

    # intra-chunk quadratic: L[i,j] = exp(cs_i - cs_j) (i >= j)
    Ls = cs - cs.T                                # (Q, Q) via (Q,1)-(1,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(Ls), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ()))) * L
    y = jax.lax.dot(scores, xdt)                  # (Q, P)

    # inter-chunk: readout of carried state, then state update
    h = h_ref[...]                                # (P, N)
    y = y + jnp.exp(cs) * jax.lax.dot_general(
        C, h, (((1,), (1,)), ((), ())))           # (Q,N)x(P,N)^T -> (Q,P)
    decay_end = jnp.exp(cs[-1:] - cs)             # (Q, 1)
    contrib = jax.lax.dot_general(
        xdt, B * decay_end, (((0,), (0,)), ((), ())))   # (P, N)
    h_ref[...] = jnp.exp(cs[-1]) * h + contrib

    y_ref[0, 0] = (y + x * D).astype(y_ref.dtype)

    @pl.when(ic == nchunks - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...].astype(hout_ref.dtype)


def ssd_pallas(x, dt, A_log, Bmat, Cmat, D, *, chunk=256, h0=None,
               return_final_state=False, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); A_log: (H,); B/C: (B,S,G,N); D: (H,).

    Groups broadcast to heads via index_map (no materialized repeat).
    h0 is unsupported in the kernel path (prefill continuation uses the
    ref); callers pass h0=None here.
    """
    assert h0 is None, "kernel path starts from h=0 (use ref for h0)"
    Bsz, S, H, P = x.shape
    _, _, G, N = Bmat.shape
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    xt = x.transpose(0, 2, 1, 3)                       # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)[..., None]             # (B,H,S,1)
    Bt = Bmat.transpose(0, 2, 1, 3)                    # (B,G,S,N)
    Ct = Cmat.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, chunk=Q, nchunks=nc)
    y, hlast = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, A_log, Bt, Ct, D)

    y = y.transpose(0, 2, 1, 3)
    if return_final_state:
        return y, hlast
    return y
