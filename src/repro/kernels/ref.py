"""Pure-jnp oracles for every Pallas kernel.

Deliberately *independent* implementations (naive full-materialization or
step-sequential), so a kernel bug cannot hide behind a shared code path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def naive_attention(q, k, v, q_pos, kv_pos, *, causal: bool = True,
                    window: int = 0, chunk: int = 0) -> jax.Array:
    """q: (B,Sq,Hq,D); k/v: (B,Skv,Hkv,D); positions (B,S*). Full scores."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * (D ** -0.5)
    qp = q_pos[:, None, :, None]
    kp = kv_pos[:, None, None, :]
    ok = jnp.ones_like(s, bool)
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > qp - window
    if chunk:
        ok &= (kp // chunk) == (qp // chunk)
    s = jnp.where(ok, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, vr.astype(jnp.float32))
    return o.astype(q.dtype)


def naive_decode_attention(q, k_cache, v_cache, kv_valid) -> jax.Array:
    """q: (B,Hkv,G,D); caches (B,S,Hkv,D); kv_valid (B,S) -> (B,Hkv,G,D)."""
    D = q.shape[-1]
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * (D ** -0.5)
    s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", w,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


def naive_topk(queries, db, db_valid, k: int) -> Tuple[jax.Array, jax.Array]:
    """queries (Q,D), db (N,D) -> (scores, idx) each (Q,k)."""
    s = jnp.einsum("qd,nd->qn", queries.astype(jnp.float32),
                   db.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(db_valid[None, :] > 0, s, -jnp.inf)
    return jax.lax.top_k(s, k)


def ssd_sequential(x, a, B, C, init_state: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Token-by-token SSD recurrence (the slow, obviously-correct oracle).

    x: (b,S,H,P) (pre-multiplied by dt); a: (b,S,H) log-decay;
    B/C: (b,S,G,N). Returns (y: (b,S,H,P), final_state: (b,H,P,N)).
    """
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)   # (b,S,H,N)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    af = a.astype(jnp.float32)
    s0 = (jnp.zeros((b, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(state, inp):
        xt, at, Bt, Ct = inp        # (b,H,P), (b,H), (b,H,N), (b,H,N)
        dA = jnp.exp(at)
        state = state * dA[..., None, None] + xt[..., None] * Bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", state, Ct)
        return state, y

    xs = (xf.transpose(1, 0, 2, 3), af.transpose(1, 0, 2),
          Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3))
    final, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), final
