"""Public jit'd wrapper: serve-layout in, kernel-layout inside.

serve.decode keeps caches (B, C, KV, hd); the kernel wants kv-head-major
(B, KV, C, hd) so each grid program streams one contiguous head row.
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp

from repro.platform import resolve_interpret
from .kernel import decode_attention_kernel

LANES = 128


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jax.Array,        # (B, KV, G, hd)   — serve.decode._project_decode layout
    k_cache: jax.Array,  # (B, C, KV, hd)
    v_cache: jax.Array,  # (B, C, KV, hd)
    valid: jax.Array,    # (B, C) bool
    *,
    interpret: Optional[bool] = None,  # platform-resolved (repro.platform)
):
    interpret = resolve_interpret(interpret)
    B, KV, G, hd = q.shape
    C = k_cache.shape[1]
    pad = (-hd) % LANES
    hd_t = hd
    if pad:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, pad)])
        k_cache = jnp.pad(k_cache, [(0, 0)] * 3 + [(0, pad)])
        v_cache = jnp.pad(v_cache, [(0, 0)] * 3 + [(0, pad)])
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, KV, C, hd)
    vt = v_cache.transpose(0, 2, 1, 3)
    ctx, mass = decode_attention_kernel(
        q, kt, vt, valid.astype(jnp.int32)[:, None, :],
        scale=1.0 / (hd_t ** 0.5), interpret=interpret,
    )
    return ctx[..., :hd_t], mass[:, 0, :]
