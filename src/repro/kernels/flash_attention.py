"""Flash attention (prefill) — Pallas TPU kernel.

Online-softmax over KV blocks with accumulators in VMEM scratch. Grid is
(batch, q_head, q_block, kv_block); the TPU executes the last grid dimension
innermost/sequentially, so scratch carries (m, l, acc) across kv blocks of one
query block. GQA is handled in the k/v index maps (q head h reads kv head
h // group). Causal / sliding-window / chunked-local masking comes from the
position operands, so ragged (non-arange) positions also work.

Block shapes: q rows ``blk_q`` (default 256), kv rows ``blk_k`` (default 512),
head_dim lanes. The kernel sees (B, H, S, D): Mosaic tiles the last two
block dims, which must be multiples of (8, 128) or span the array, so a
(1, blk, 1, D) block over the model's (B, S, H, D) layout does not compile.
VMEM working set ≈ blk_q·D (q) + 2·blk_k·D (k,v) + blk_q·blk_k (scores) +
blk_q·D (acc) floats ≈ 1.1 MB at defaults — comfortably under the ~16 MB/core
budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
            window: int, chunk: int, n_kv_blocks: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                # (blk_q, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (blk_k, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qp = qpos_ref[0].astype(jnp.int32).T                # (blk_q, 1)
    kp = kpos_ref[0].astype(jnp.int32)                  # (1, blk_k)
    ok = kp < jnp.int32(2**30)        # padded kv rows are always invalid
    ok = jnp.broadcast_to(ok, s.shape)
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > qp - window
    if chunk:
        ok &= (kp // chunk) == (qp // chunk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[:, 0]                                # (blk_q,)
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(ok, p, 0.0)
    l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_cur

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, *,
                    causal: bool = True, window: int = 0, chunk: int = 0,
                    blk_q: int = 256, blk_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Sq,Hq,D); k/v: (B,Skv,Hkv,D); positions (B,S). -> (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    pad_q = (-Sq) % blk_q
    pad_k = (-Skv) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_k)),
                         constant_values=2**30)  # masked by causal compare
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    nQ, nK = Sq_p // blk_q, Skv_p // blk_k

    # Mosaic tiles the last two block dims, which must be multiples of
    # (8, 128) or span the array: so heads go ahead of the sequence, and
    # each position row gets a unit axis ahead of it.
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    q_pos, kv_pos = q_pos[:, None, :], kv_pos[:, None, :]
    grid = (B, Hq, nQ, nK)
    kern = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window, chunk=chunk,
        n_kv_blocks=nK)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, blk_q), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, blk_k), lambda b, h, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # m
            pltpu.VMEM((blk_q, 1), jnp.float32),   # l
            pltpu.VMEM((blk_q, D), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(q, k, v, q_pos, kv_pos)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
