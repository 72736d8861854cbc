"""Pallas TPU flash attention (GQA, causal, sliding-window, soft-cap).

TPU mapping (DESIGN.md §2 — HW adaptation notes):
  * grid = (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
    'arbitrary' (sequential) so the online-softmax accumulator lives in
    VMEM scratch across kv steps;
  * BlockSpecs tile q/k/v into (block_q x head_dim) / (block_k x head_dim)
    VMEM tiles, MXU-aligned (block sizes multiples of 128 where the shape
    allows);
  * GQA is an index_map: the kv BlockSpec maps q-head h to kv-head
    h // group, so no materialized head expansion ever touches HBM;
  * causal/window masking is applied in-kernel; fully-masked kv blocks are
    skipped via `pl.when` (on TPU the block's DMA still issues — a
    production variant would prune the grid; the CPU execution path
    (ref.py) does prune, which keeps the dry-run roofline honest).

Validated against ref.flash_attention_ref with interpret=True on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            block_q: int, block_k: int, seq_k: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # static skip: block fully masked under causal/window?
    run = True
    if causal:
        run = jnp.logical_and(True, (ik * block_k) <=
                              (q_offset + iq * block_q + block_q - 1))
    if window > 0:
        run = jnp.logical_and(
            run, (ik * block_k + block_k - 1) >=
                 (q_offset + iq * block_q - window + 1))

    @pl.when(run if not isinstance(run, bool) else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq,) in (bq,1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-37)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, q_offset=0,
                           softcap=0.0, block_q=128, block_k=128,
                           interpret=False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    scale = 1.0 / (D ** 0.5)

    # layout: (B, H, S, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_k=Sk,
        q_offset=int(q_offset) if isinstance(q_offset, int) else 0)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
