"""jit'd kernel entry points with backend dispatch.

On TPU backends the Pallas kernels compile natively; everywhere else they run
in ``interpret=True`` mode (the kernel *body* executes op-by-op on CPU), which
is what the test suite sweeps against the ``ref.py`` oracles. Set
``REPRO_FORCE_REF=1`` to bypass kernels entirely (used to A/B the model paths).
"""
from __future__ import annotations

import os

import jax

from repro.configs.base import ModelConfig
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk_similarity as _topk
from repro.kernels import topk_similarity_i4 as _topk_i4
from repro.kernels import topk_similarity_i8 as _topk_i8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _force_ref() -> bool:
    return os.environ.get("REPRO_FORCE_REF", "0") == "1"


def flash_attention(q, k, v, q_pos, kv_pos, cfg: ModelConfig, *,
                    causal: bool = True):
    if _force_ref():
        return _ref.naive_attention(q, k, v, q_pos, kv_pos, causal=causal,
                                    window=cfg.sliding_window,
                                    chunk=cfg.attention_chunk)
    return _fa.flash_attention(
        q, k, v, q_pos, kv_pos, causal=causal, window=cfg.sliding_window,
        chunk=cfg.attention_chunk, interpret=_interpret())


def decode_attention(q, k_cache, v_cache, kv_valid, cfg: ModelConfig):
    """q: (B,1,Hq,D) -> (B,1,Hq,D) (model-layer layout)."""
    B, _, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    if _force_ref():
        o = _ref.naive_decode_attention(qg, k_cache, v_cache, kv_valid)
    else:
        o = _dec.decode_attention(qg, k_cache, v_cache, kv_valid,
                                  interpret=_interpret())
    return o.reshape(B, 1, Hq, D)


def _check_k(k: int) -> None:
    """The top-k kernels keep a K_PAD-wide running top-k in VMEM scratch;
    a larger k is refused rather than silently served by the oracle."""
    if k > _topk.K_PAD:
        raise ValueError(f"top-k kernels hold at most {_topk.K_PAD} results "
                         f"per query, got k={k}")


def topk_similarity(queries, db, db_valid, k: int):
    _check_k(k)
    if _force_ref():
        return _ref.naive_topk(queries, db, db_valid, k)
    return _topk.topk_similarity(queries, db, db_valid, k,
                                 interpret=_interpret())


def topk_similarity_i8(queries, db_i8, db, db_valid, k: int):
    """Exact two-phase int8 top-k (see ``topk_similarity_i8.py``).

    Under ``REPRO_FORCE_REF`` phase 1 runs as plain jnp instead of the
    Pallas kernel — the two-phase result stays exact either way (the
    margin check certifies the candidate set, however it was produced).
    """
    _check_k(k)
    return _topk_i8.topk_similarity_i8(
        queries, db_i8, db, db_valid, k, interpret=_interpret(),
        use_kernel_phase1=not _force_ref())


def topk_similarity_i4(queries, db_i4, db, db_valid, k: int):
    """Exact two-phase int4 cold-tier top-k (``topk_similarity_i4.py``).

    Same dispatch contract as the int8 entry: under ``REPRO_FORCE_REF``
    phase 1 runs as plain jnp, and the result stays exact either way —
    the margin certificate (or fp32 fallback) covers the candidate set
    however it was produced.
    """
    _check_k(k)
    return _topk_i4.topk_similarity_i4(
        queries, db_i4, db, db_valid, k, interpret=_interpret(),
        use_kernel_phase1=not _force_ref())


def ssd_scan(x, a, B, C, *, chunk: int = 128):
    if _force_ref():
        return _ref.ssd_sequential(x, a, B, C)
    return _ssd.ssd_scan(x, a, B, C, chunk=chunk, interpret=_interpret())
