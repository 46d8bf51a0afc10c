"""Text embeddings of the world's descriptions, and the entity banks.

A copy of ``OracleEmbedder``'s hashing (``semantic/embed.py``): a text's
vector is a unit Gaussian vector seeded by the BLAKE2b digest of
``"<salt>:<text>"``, so the same text always gets the same vector. Entity
embeddings add per-entity Gaussian noise to their description's vector and
renormalise, as ``OracleEmbedder(noise=...)`` does, so that no two
entities tie exactly (a real embedder gives every object its own vector).
The banks are made on the device in one jitted call from the seed.
"""
from __future__ import annotations

import hashlib
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def text_vector(text: str, dim: int, salt: int) -> np.ndarray:
    """float64 unit vector of ``text`` (OracleEmbedder._base)."""
    h = hashlib.blake2b(f"{salt}:{text.strip().lower()}".encode(),
                        digest_size=8).digest()
    v = np.random.default_rng(int.from_bytes(h, "little")).standard_normal(dim)
    return v / np.linalg.norm(v)


def text_vectors(texts: List[str], dim: int, salt: int) -> np.ndarray:
    """(n, dim) float32 unit vectors, one per text."""
    if not texts:
        return np.zeros((0, dim), np.float32)
    return np.stack([text_vector(t, dim, salt) for t in texts]
                    ).astype(np.float32)


class BenchEmbedder:
    """The embedder the harness hands to the engine: hash vectors of the
    query texts, without noise. ``spans`` wraps each call in a profiler
    annotation so a traced run shows the embedder's host time."""

    def __init__(self, dim: int, salt: int):
        self.dim, self.salt = dim, salt
        self.texts_of = {}          # vector bytes -> text, for the check

    def embed_texts(self, texts: List[str],
                    rng: Optional[np.random.Generator] = None) -> np.ndarray:
        with jax.profiler.TraceAnnotation("bench.embed"):
            out = text_vectors(list(texts), self.dim, self.salt)
        for t, v in zip(texts, out):
            self.texts_of[v.tobytes()] = t
        return out

    def embed_for_image(self, texts: List[str]) -> np.ndarray:
        return self.embed_texts([t + " appearance" for t in texts])


def salt_of(seed: int) -> int:
    return seed % (1 << 62)


def bank_key(seed: int, role: str) -> jax.Array:
    """A PRNG key for one bank; seeds beyond 32 bits are split in two."""
    s = seed % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, {"text": 1, "image": 2}[role])


@partial(jax.jit, static_argnames=("noise",))
def _noisy_rows(bases, desc_idx, key, noise: float):
    x = jnp.take(bases, desc_idx, axis=0)
    x = x + noise * jax.random.normal(key, x.shape, jnp.float32)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def entity_bank(texts: List[str], desc_idx: np.ndarray, dim: int, seed: int,
                noise: float, role: str = "text") -> jax.Array:
    """(N, dim) float32 entity embeddings on the default device: row ``i``
    is description ``desc_idx[i]``'s vector (of ``text + " appearance"`` for
    the image bank) plus noise. The same arguments give the same bits."""
    suffix = "" if role == "text" else " appearance"
    bases = jnp.asarray(text_vectors([t + suffix for t in texts], dim,
                                     salt_of(seed)))
    return _noisy_rows(bases, jnp.asarray(desc_idx, jnp.int32),
                       bank_key(seed, role), float(noise))
