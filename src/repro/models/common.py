"""Shared numerics: norms, activations, initializers, dtype discipline.

Convention: parameters live in ``param_dtype`` (bf16), matmuls run in the model
``dtype`` (bf16), normalization / softmax / losses run in f32.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dt(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back).
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_norm(x: jax.Array, p: Params, norm_type: str, eps: float) -> jax.Array:
    if norm_type == "rmsnorm":
        return rms_norm(x, p["scale"], eps)
    return layer_norm(x, p["scale"], p["bias"], eps)


def init_norm(d: int, norm_type: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# Initializers.
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def with_sharding_constraint(x, spec):
    """Sharding constraint that adapts to the ambient mesh.

    Axis names not present in the current mesh are dropped (so code written
    for the multi-pod mesh also lowers single-pod), and axes that would shard
    a dimension unevenly are dropped (so batch-1 shapes stay replicated).
    No-op without a mesh context.
    """
    from repro.compat import get_ambient_mesh
    mesh = get_ambient_mesh()
    names = dict(zip(mesh.axis_names, mesh.axis_sizes))
    if not names:
        return x
    clean = []
    for dim, ax in zip(x.shape, tuple(spec) + (None,) * x.ndim):
        if ax is None:
            clean.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        kept, size = [], 1
        for a in axes:
            if a in names and dim % (size * names[a]) == 0:
                kept.append(a)
                size *= names[a]
        clean.append(tuple(kept) if len(kept) > 1 else
                     (kept[0] if kept else None))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*clean))
