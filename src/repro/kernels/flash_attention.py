"""Pallas TPU flash attention: GQA + causal + sliding-window.

TPU-native design (not a CUDA port):
  * Inputs flattened to (B*KV, G, S, hd): one program per (batch x kv-head,
    q-block); the q tile (and its G grouped query heads) live in VMEM.
  * K/V for the program's kv-head are VMEM-resident (S<=32k x hd=128 bf16 =
    8 MB — fits v5e's ~128 MB VMEM alongside tiles), streamed MXU-tile by
    tile with an online-softmax running (max, denom) in fp32 VREGs.
  * Causal/sliding-window masking is applied per kv-tile; fully-masked kv
    tiles are SKIPPED (loop bounds depend on the q-block index), so SWA does
    ~window/S of the full-attention work — the structural saving, not a mask.
  * MXU alignment: block_q x block_k = 128 x 128 (head_dim padded to 128).

Validated in interpret mode against flash_attention_ref.reference (tests/
test_kernels.py sweeps shapes/dtypes/window/causality).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
            window: int, block_k: int, seq_k: int):
    """One (batch*kv_head, q_block) program.

    q_ref: (1, G, block_q, hd) | k_ref/v_ref: (1, seq_k, hd).
    """
    _, G, block_q, hd = q_ref.shape
    q_blk_idx = pl.program_id(1)
    q_start = q_blk_idx * block_q

    q = q_ref[0].astype(jnp.float32) * scale             # (G, bq, hd)

    # kv range this q-block can see
    lo = 0
    if window > 0:
        lo = jnp.maximum(q_start + 1 - window, 0) // block_k
    hi = seq_k // block_k
    if causal:
        hi = jnp.minimum(hi, (q_start + block_q + block_k - 1) // block_k)

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k_start = kb * block_k
        k = k_ref[0, pl.ds(k_start, block_k)].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, pl.ds(k_start, block_k)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # s: (G, bq, bk) — mask
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                               block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                               block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok[None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)                      # (G, bq)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])                # (G, bq, bk)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return acc, m_new, l_new

    acc0 = jnp.zeros((G, block_q, hd), jnp.float32)
    m0 = jnp.full((G, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, block_q), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,S,KV,hd); H = KV*G. Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0

    # (B,S,H,hd) -> (B*KV, G, S, hd)
    qf = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KV, G, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    grid = (B * KV, S // block_q)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_k=block_k, seq_k=S),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, S, hd), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, block_q, hd), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, block_q, hd), lambda b, i: (b, 0, i, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B, S, H, hd)
