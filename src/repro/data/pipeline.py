"""Deterministic synthetic data pipeline.

Every batch is a pure function of ``(seed, step)`` — restart-safe by
construction: after a failure + checkpoint restore at step k, the
pipeline regenerates exactly the batches k, k+1, ... with no replay or
skip bookkeeping.  Each host can generate only its shard (host_id,
n_hosts) for multi-host scale-out.

Also provides document packing (concatenate-and-split with EOS
boundaries) so the training examples exercise a real batching path.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class SyntheticTokens:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    host_id: int = 0
    n_hosts: int = 1
    # Markov-ish structure so the loss actually decreases during the
    # end-to-end training example (pure uniform noise cannot be learnt).
    structure: float = 0.8

    def batch(self, step: int) -> dict:
        assert self.global_batch % self.n_hosts == 0
        local_b = self.global_batch // self.n_hosts
        rng = np.random.default_rng(
            np.uint64(self.seed * 1_000_003 + step) * np.uint64(2654435761)
            + np.uint64(self.host_id))
        shape = (local_b, self.seq_len + 1)
        noise = rng.integers(0, self.vocab_size, size=shape, dtype=np.int64)
        # structured component: next token = (prev * 31 + 7) % vocab
        toks = np.empty(shape, dtype=np.int64)
        toks[:, 0] = noise[:, 0]
        use_rule = rng.random(shape) < self.structure
        for t in range(1, shape[1]):
            ruled = (toks[:, t - 1] * 31 + 7) % self.vocab_size
            toks[:, t] = np.where(use_rule[:, t], ruled, noise[:, t])
        return {
            "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32),
        }

    def __iter__(self) -> Iterator[dict]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


def pack_documents(docs: list[np.ndarray], seq_len: int, eos: int,
                   pad: int = 0) -> np.ndarray:
    """Concatenate docs with EOS separators and split into rows."""
    stream: list[int] = []
    for d in docs:
        stream.extend(int(x) for x in d)
        stream.append(eos)
    n_rows = max(1, len(stream) // seq_len)
    stream = stream[: n_rows * seq_len]
    if not stream:
        stream = [pad] * seq_len
        n_rows = 1
    return np.asarray(stream, dtype=np.int32).reshape(n_rows, seq_len)


def batch_for_shape(cfg, shape, *, step: int = 0, seed: int = 0) -> dict:
    """A concrete (allocated) batch for an (arch, shape) cell — used by
    CPU-scale examples and tests; ``launch.specs.input_specs`` gives the
    shape-only stand-ins."""
    gen = SyntheticTokens(cfg.vocab_size, shape.seq_len, shape.global_batch,
                          seed=seed)
    batch = gen.batch(step)
    if cfg.vlm is not None:
        n_p = cfg.vlm.n_patches
        batch["tokens"] = batch["tokens"][:, : shape.seq_len - n_p]
        batch["labels"] = batch["labels"][:, : shape.seq_len - n_p]
        key = jax.random.PRNGKey(seed + step)
        batch["patch_embeds"] = jax.random.normal(
            key, (shape.global_batch, n_p,
                  cfg.vlm.patch_embed_dim or cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        key = jax.random.PRNGKey(seed + step)
        batch["frames"] = jax.random.normal(
            key, (shape.global_batch, cfg.encdec.enc_len, cfg.d_model),
            jnp.float32)
    return batch
