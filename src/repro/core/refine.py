"""Relationship refinement — the 'lazy' VLM stage (Section 2.3).

After the symbolic stage has pruned the search space to a candidate set of
(vid, fid, sid, rl, oid) rows, each candidate is verified:

  * ``VLMVerifier`` — a real JAX VLM (any registry arch; tests use a reduced
    qwen2.5-vl-7b, the paper's own choice): frame patch embeddings (stub
    frontend) + a tokenized "is <subj> <rel> <obj>?" prompt, one prefill, and
    a yes/no logit comparison. Candidates are padded into fixed-size batches
    so the jitted program is reused across queries.
  * ``MockVerifier`` — ground-truth oracle with an optional flip rate; used to
    test pipeline logic independently of model quality.

Laziness is measurable: ``calls`` counts VLM-verified frames; benchmarks
compare it against the frames an end-to-end VLM would ingest.

Against a real endpoint, either verifier should sit behind the fault
layer's retry/backoff/breaker envelope — ``FaultTolerantVerifier`` (same
``verify``/``calls`` contract, re-exported here from
:mod:`repro.core.fault`), which the engine applies automatically when
constructed with a ``fault_policy``; ``FlakyVerifier`` is the seeded
chaos double the robustness tests wrap around ``MockVerifier``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.fault import (FaultPolicy,  # noqa: F401  (re-exports)
                              FaultTolerantVerifier, FlakyVerifier)
from repro.models import model as M
from repro.semantic.tokenizer import HashTokenizer
from repro.video.synth import PREDICATES, SyntheticWorld


class MockVerifier:
    def __init__(self, world: SyntheticWorld, flip_prob: float = 0.0,
                 seed: int = 0):
        self.world = world
        self.flip_prob = flip_prob
        self.rng = np.random.default_rng(seed)
        self.calls = 0

    def verify(self, rows: np.ndarray) -> np.ndarray:
        self.calls += len(rows)
        out = self.world.verify_batch(rows)
        if self.flip_prob:
            flips = self.rng.random(len(rows)) < self.flip_prob
            out = out ^ flips
        return out


class VLMVerifier:
    """Batched VLM yes/no verification with a jitted prefill."""

    def __init__(self, cfg: ModelConfig, params=None, *, world: SyntheticWorld,
                 entity_desc, batch_size: int = 16, prompt_len: int = 24,
                 key=None, use_kernels: bool = False):
        assert cfg.vision.enabled and cfg.vision.kind == "patches"
        self.cfg = cfg
        self.world = world
        self.entity_desc = entity_desc  # (vid, eid) -> description text
        self.batch_size = batch_size
        self.prompt_len = prompt_len
        self.tokenizer = HashTokenizer(cfg.vocab_size)
        self.yes_id = self.tokenizer.token_id("yes")
        self.no_id = self.tokenizer.token_id("no")
        if params is None:
            params = M.init_params(key or jax.random.PRNGKey(11), cfg)
        self.params = params
        self.calls = 0

        P = cfg.vision.num_positions
        S = P + prompt_len

        def _scores(params, tokens, patches, mrope_positions):
            batch = {"tokens": tokens, "patch_embeds": patches,
                     "mrope_positions": mrope_positions}
            logits, _ = M.prefill(params, batch, self.cfg, cache_len=S + 1,
                                  use_kernels=use_kernels)
            lf = logits[:, -1].astype(jnp.float32)
            return lf[:, self.yes_id] - lf[:, self.no_id]

        self._scores = jax.jit(_scores)
        self._seq_len = S

    def _prompt(self, vid: int, sid: int, rl: int, oid: int) -> str:
        sdesc = self.entity_desc.get((vid, sid), f"object {sid}")
        odesc = self.entity_desc.get((vid, oid), f"object {oid}")
        return f"question is the {sdesc} {PREDICATES[rl]} the {odesc} answer"

    def verify(self, rows: np.ndarray) -> np.ndarray:
        """rows: (M, 5) -> bool (M,): the yes-minus-no margin is positive."""
        self.calls += len(rows)
        return self.margins(rows) > 0

    def margins(self, rows: np.ndarray) -> np.ndarray:
        """rows: (M, 5) -> fp32 (M,) last-position logit of "yes" minus
        that of "no". Pads to batch_size multiples."""
        m = len(rows)
        out = np.zeros((m,), np.float32)
        bs = self.batch_size
        for lo in range(0, m, bs):
            chunk = rows[lo: lo + bs]
            scores = np.asarray(self._scores(self.params,
                                             *self.batch_inputs(chunk)))
            out[lo: lo + len(chunk)] = scores[: len(chunk)]
        return out

    def batch_inputs(self, chunk: np.ndarray):
        """(tokens, patches, mrope_positions) for at most ``batch_size``
        candidate rows, padded to a full batch."""
        cfg = self.cfg
        P, D = cfg.vision.num_positions, cfg.vision.embed_dim
        bs = self.batch_size
        toks, patches = [], []
        for (vid, fid, sid, rl, oid) in chunk:
            ids, _ = self.tokenizer.encode(
                self._prompt(int(vid), int(sid), int(rl), int(oid)),
                self.prompt_len)
            toks.append(ids)
            patches.append(self.world.frame_patches(int(vid), int(fid), P, D))
        for _ in range(bs - len(chunk)):
            toks.append(np.zeros((self.prompt_len,), np.int32))
            patches.append(np.zeros((P, D), np.float32))
        tokens = jnp.asarray(np.stack(toks))
        patch = jnp.asarray(np.stack(patches), jnp.bfloat16)
        S = self._seq_len
        mrope = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, None],
                                 (3, bs, S))
        return tokens, patch, mrope
