"""Expert-parallel MoE via shard_map (beyond-GSPMD optimization, §Perf H1).

The einsum/scatter MoE in ``repro.models.moe`` is correct but its
data-dependent gather/scatter defeats GSPMD's locality analysis: the compiler
falls back to all-gathering the token array and the (E, C, D) expert buffer
per MoE layer (~25 GB/layer/µbatch measured on qwen3-moe train_4k).

Layout insight: in this framework's TP scheme the residual stream is already
*replicated across the model axis* (activations sharded over data only), so
textbook all-to-all EP is unnecessary. Each model shard:

  1. routes its (replicated) local tokens with the (replicated) router,
  2. selects only the (token, choice) pairs whose expert lives on this shard,
  3. buckets them per local expert with fixed capacity (static shapes),
  4. runs the dense batched expert FFN over (E_loc, C, D),
  5. scatter-adds gate-weighted results into a (T_loc, D) f32 buffer,
  6. one ``psum`` over the model axis combines shards' contributions.

On-wire bytes per device per layer = T_loc·D·4 (the psum) ≈ 67 MB at
train_4k scale — ~370× less than the GSPMD fallback. Routing decisions are
bit-identical to the reference path; capacity is enforced per expert (the
same semantics), so outputs match ``moe_layer`` up to capacity-drop ordering.
"""
from __future__ import annotations

from typing import Tuple

import os

import jax
import jax.numpy as jnp
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import Params, activation


def ep_enabled(cfg: ModelConfig, x_shape) -> bool:
    """EP path applies when opted in and the layout divides cleanly."""
    if os.environ.get("REPRO_MOE_EP", "0") != "1":
        return False
    from repro.compat import get_ambient_mesh
    am = get_ambient_mesh()
    if "model" not in am.axis_names:
        return False
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    n = sizes["model"]
    dp = 1
    for a in ("pod", "data"):
        dp *= sizes.get(a, 1)
    B, S = x_shape[0], x_shape[1]
    return (cfg.moe.num_experts % n == 0) and ((B * S) % dp == 0)


def moe_layer_ep(p: Params, x: jax.Array, cfg: ModelConfig, mesh,
                 data_axes: Tuple[str, ...] = ("data",),
                 model_axis: str = "model"):
    """Drop-in EP replacement for ``moe_layer``. Returns (out, aux)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.experts_per_token
    n_shards = mesh.shape[model_axis]
    e_loc = E // n_shards
    act = activation(cfg.mlp_activation)

    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    T_loc = max(1, (B * S) // dp)
    cap = int(T_loc * K * m.capacity_factor / E)
    cap = max(8, ((cap + 7) // 8) * 8)

    def fn(xt, router, wg, wu, wd):
        # xt (T_loc, D) — replicated over model; w* (e_loc, ...) — this shard
        my = jax.lax.axis_index(model_axis)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)        # (T_loc, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        flat_e = expert_ids.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_loc), K)
        flat_g = gate_vals.reshape(-1)
        mine = (flat_e // e_loc) == my
        loc_e = jnp.where(mine, flat_e % e_loc, e_loc)         # e_loc = drop
        order = jnp.argsort(loc_e, stable=True)
        le, lt, lg = loc_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(le, length=e_loc + 1)[:e_loc]
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(T_loc * K) - starts[jnp.minimum(le, e_loc - 1)]
        keep = (rank < cap) & (le < e_loc)
        slot = jnp.where(keep, le * cap + rank, e_loc * cap)

        buf = jnp.zeros((e_loc * cap + 1, D), xt.dtype).at[slot].set(xt[lt])
        xin = buf[: e_loc * cap].reshape(e_loc, cap, D)
        h = act(jnp.einsum("ecd,edf->ecf", xin, wg))
        h = h * jnp.einsum("ecd,edf->ecf", xin, wu)
        eout = jnp.einsum("ecf,efd->ecd", h, wd).reshape(-1, D)

        contrib = jnp.where(
            keep[:, None],
            eout[jnp.minimum(slot, e_loc * cap - 1)].astype(jnp.float32)
            * lg[:, None], 0.0)
        out = jnp.zeros((T_loc, D), jnp.float32).at[lt].add(contrib)
        out = jax.lax.psum(out, model_axis)

        # Switch aux loss (identical on every model shard; psum over data)
        me_frac = probs.mean(0)
        ce = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T_loc * K)
        aux = (me_frac * ce).sum() * E * m.router_aux_loss
        aux = jax.lax.pmean(aux, data_axes)
        return out.astype(x.dtype), aux

    dspec = P(data_axes)
    fn_sharded = shard_map(
        fn, mesh=mesh,
        in_specs=(dspec, P(), P(model_axis), P(model_axis), P(model_axis)),
        out_specs=(dspec, P()),
        check_replication=False)
    xt = x.reshape(B * S, D)
    out, aux = fn_sharded(xt, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"])
    out = out.reshape(B, S, D)
    if m.shared_expert_d_ff:
        from repro.models.mlp import mlp
        out = out + mlp(p["shared"], x, cfg)
    return out, aux
