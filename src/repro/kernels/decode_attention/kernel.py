"""Pallas TPU decode attention with attention-mass output.

The serving hot-spot of the SS± heavy-hitter KV cache: one new token
attends over the budgeted cache (C = hh_kv_budget, e.g. 8192 slots) and
the kernel emits, besides the context, the per-slot probability mass —
the weighted-insert stream of the SpaceSaving± sketch (serve/h2o.py).

TPU mapping:
  - grid (B, KV): one program per (sequence, kv-head); the whole cache
    row (C, hd) sits in VMEM — for the SS± budget C <= 16k that is
    <= 8 MB (k+v bf16 at hd=128), the design point of this kernel.
    (Unbudgeted 32k+ dense caches belong to a streamed variant; the SS±
    cache exists precisely so serving never needs one.)
  - scores tile (G, C) f32 in VMEM; single-shot softmax (no online
    rescaling needed since C is VMEM-resident).
  - mass accumulates over kv-heads: output revisited across the KV grid
    dim (sequential) with an accumulate-into-output pattern.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.platform import resolve_interpret

F32 = jnp.float32
NEG_INF = -1e9


def _kernel(q_ref, k_ref, v_ref, valid_ref, ctx_ref, mass_ref, *, scale):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        mass_ref[...] = jnp.zeros_like(mass_ref)

    q = q_ref[0, 0].astype(F32)                    # (G, hd)
    k = k_ref[0, 0].astype(F32)                    # (C, hd) this kv head
    v = v_ref[0, 0].astype(F32)
    ok = valid_ref[0] != 0                         # (1, C)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
    ) * scale                                       # (G, C)
    s = jnp.where(ok, s, NEG_INF)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    denom = p.sum(axis=1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    p = jnp.where(ok.any(), p, 0.0)

    ctx = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )                                               # (G, hd)
    ctx_ref[0, 0] = ctx.astype(ctx_ref.dtype)
    # accumulate over kv heads
    mass_ref[0] = mass_ref[0] + p.sum(axis=0, keepdims=True)


def decode_attention_kernel(
    q: jax.Array,        # (B, KV, G, hd)
    k_cache: jax.Array,  # (B, KV, C, hd)  — kv-head-major layout
    v_cache: jax.Array,  # (B, KV, C, hd)
    valid: jax.Array,    # (B, 1, C) int32
    *,
    scale: float = 0.0,
    interpret: Optional[bool] = None,
):
    """Returns ``(ctx (B, KV, G, hd), mass (B, 1, C))``.

    ``valid`` and ``mass`` carry a unit middle axis so their blocks are
    ``(1, 1, C)``: the last two block dims then equal the array's, which
    the TPU's (8, 128) tiling rule accepts for any B (a ``(1, C)`` block
    of a ``(B, C)`` array does not).
    """
    interpret = resolve_interpret(interpret)
    B, KV, G, hd = q.shape
    C = k_cache.shape[2]
    scale = scale or 1.0 / math.sqrt(hd)
    kern = functools.partial(_kernel, scale=scale)
    ctx, mass = pl.pallas_call(
        kern,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, k: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, k: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, k: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b, k: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, k: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b, k: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, hd), v_cache.dtype),
            jax.ShapeDtypeStruct((B, 1, C), F32),
        ],
        interpret=interpret,
    )(q, k_cache, v_cache, valid)
    return ctx, mass
