"""Pallas TPU flash attention (online softmax, GQA, causal + SWA).

Grid (BH, nq, nkv) with the kv dim sequential ("arbitrary"): each (batch
x head, q-block) streams kv blocks through VMEM, keeping the running
(m, l, acc) in scratch — the HBM traffic is Q+K+V+O only, never the
(S, T) score matrix (the memory-term killer the roofline analysis flags
on the jnp path; see EXPERIMENTS.md §Perf).

TPU mapping choices:
  - q/k/v blocks (bq, hd) / (bkv, hd) with hd padded to lane width 128;
    bq = bkv = 128 keeps the (bq, bkv) score tile MXU-aligned.
  - GQA without materializing expanded K/V: the k/v BlockSpec index_map
    folds the q-head -> kv-head mapping (bh // group).
  - causal + sliding-window masks built from block-offset iotas; fully
    masked tiles still visit (static grid) but skip the matmul via
    pl.when — on TPU this saves the MXU issue, the canonical pattern.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import resolve_interpret

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bkv: int, nkv: int, causal: bool, window: int,
            scale: float, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this tile
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + q_offset
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    allowed = jnp.ones((bq, bkv), bool)
    if causal:
        allowed &= kpos <= qpos
    if window:
        allowed &= kpos > qpos - window

    # tile visibility: skip compute when nothing is allowed
    @pl.when(allowed.any())
    def _compute():
        q = q_ref[0].astype(F32)                     # (bq, hd)
        k = k_ref[0].astype(F32)                     # (bkv, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
        ) * scale                                     # (bq, bkv)
        s = jnp.where(allowed, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=1)
        v = v_ref[0].astype(F32)                     # (bkv, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
        )
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nkv - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,             # (BH, S, hd)  — heads folded into batch
    k: jax.Array,             # (BKV, T, hd)
    v: jax.Array,             # (BKV, T, hd)
    *,
    group: int,               # q-heads per kv-head (GQA)
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bkv: int = 128,
    scale: float = 0.0,       # 0 -> 1/sqrt(hd); pass explicitly when hd padded
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    BH, S, hd = q.shape
    T = k.shape[1]
    bq = min(bq, S)
    bkv = min(bkv, T)
    assert S % bq == 0 and T % bkv == 0, (S, bq, T, bkv)
    nq, nkv = S // bq, T // bkv
    scale = scale or 1.0 / math.sqrt(hd)
    q_offset = T - S  # align sequence ends (prefill: T == S)

    kern = functools.partial(
        _kernel, bq=bq, bkv=bkv, nkv=nkv, causal=causal,
        window=window, scale=scale, q_offset=q_offset,
    )
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), F32),      # m: running max
            pltpu.VMEM((bq,), F32),      # l: running denominator
            pltpu.VMEM((bq, hd), F32),   # acc: running numerator
        ],
        interpret=interpret,
    )(q, k, v)
