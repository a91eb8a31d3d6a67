"""Blocked causal GQA flash attention as a Pallas TPU kernel.

TPU adaptation of the training/prefill flop hotspot: q/k/v blocks are
staged HBM->VMEM via BlockSpecs, the (bq, bk) score tile and the online
softmax state (m, l, acc) live in VMEM scratch, and both matmuls hit the
MXU with 128-aligned tiles. The kv-block loop is the innermost grid
dimension so the accumulator carries across it.

Validated under interpret=True on CPU against ref.flash_attention_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool = False):
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Skv, hd)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    grid = (B * Hq, Sq // bq, Skv // bk)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda bh, qi, ki: (bh // Hq, bh % Hq, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bh, qi, ki: (bh // Hq, (bh % Hq) // rep,
                                             ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bh, qi, ki: (bh // Hq, (bh % Hq) // rep,
                                             ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bh, qi, ki: (bh // Hq, bh % Hq,
                                                   qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
