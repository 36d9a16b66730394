"""The batched LM engine: prefill once, jitted greedy decode with a
shared KV cache, per-sequence stop handling. The generation half of the
RAG-serving integration (examples/rag_serve.py); the retrieval half is
``serving.engine.AnnsFrontend``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode_step, prefill


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    eos_id: int = -1           # -1: never stop early
    temperature: float = 0.0   # 0 => greedy


class Engine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self._dec = jax.jit(
            lambda p, t, c, i: decode_step(p, t, c, i, cfg))

    def generate(self, batch: Dict[str, jax.Array],
                 rng: Optional[jax.Array] = None) -> np.ndarray:
        """batch: prompt inputs ({"tokens": [B, S]}, + modality stubs).
        Returns generated token ids [B, <=max_new_tokens]."""
        cfg, scfg = self.cfg, self.scfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, cache = prefill(self.params, batch, cfg,
                                max_len=s + scfg.max_new_tokens)
        out = []
        done = np.zeros(b, bool)
        tok = self._sample(logits[:, -1:], rng)
        for i in range(scfg.max_new_tokens):
            out.append(np.asarray(tok[:, 0]))
            if scfg.eos_id >= 0:
                done |= out[-1] == scfg.eos_id
                if done.all():
                    break
            logits, cache = self._dec(self.params, tok, cache, s + i)
            tok = self._sample(logits, rng)
        gen = np.stack(out, axis=1)
        if scfg.eos_id >= 0:  # mask post-EOS tokens
            seen = np.cumsum(gen == scfg.eos_id, axis=1) > 0
            mask = np.concatenate(
                [np.zeros((b, 1), bool), seen[:, :-1]], axis=1)
            gen = np.where(mask, scfg.eos_id, gen)
        return gen

    def _sample(self, logits, rng):
        logits = logits[:, :, : self.cfg.vocab_size]
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        assert rng is not None, "temperature sampling needs an rng"
        return jax.random.categorical(
            rng, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)
