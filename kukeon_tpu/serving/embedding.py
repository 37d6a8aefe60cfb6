"""Embedding engine: batched sentence-embedding serving over a BERT encoder.

The encoder-side sibling of :class:`~kukeon_tpu.serving.engine.ServingEngine`
(BASELINE config 5: "Llama-3-8B chat + bge-base embedding cell"). Encoders
have no decode loop, so the engine's whole job is shaping traffic onto the
MXU:

- **Fixed-shape programs**: requests are padded to (batch_size, bucket)
  grids — one compiled program per sequence bucket, never per request mix.
- **Micro-batching**: a burst of N texts runs in ceil(N / batch_size) grid
  dispatches; the padding mask keeps ragged tails exact.
- **Sharded params**: megatron column->row over the mesh's 'tensor' axis
  (parallel.sharding.bert_param_specs); XLA inserts the psums over ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from kukeon_tpu.models import bert
from kukeon_tpu.parallel import sharding as shd

EMBED_BUCKETS = (16, 32, 64, 128, 256, 512)


def bucket_length(n: int, max_len: int) -> int:
    for b in EMBED_BUCKETS:
        if n <= b:
            return min(b, max_len)
    return max_len


class EmbeddingEngine:
    """Batched embed over a jitted BERT; one engine per model cell."""

    def __init__(
        self,
        cfg: bert.BertConfig,
        params,
        mesh: Mesh,
        *,
        batch_size: int = 16,
        pooling: str = "cls",
    ):
        if mesh is None:
            raise ValueError("EmbeddingEngine requires a mesh")
        self.cfg = cfg
        self.mesh = mesh
        self.batch_size = batch_size
        self.pooling = pooling
        self.params = shd.shard_bert_params(params, mesh)

        def embed_fn(params, tokens, mask):
            return bert.embed(params, cfg, tokens, mask, pooling=self.pooling)

        self._embed = jax.jit(embed_fn)

    def warmup(self, lengths: tuple[int, ...] = (64,)) -> None:
        """Pre-compile the grid program for each bucket the lengths hit."""
        for n in lengths:
            b = bucket_length(n, self.cfg.max_position_embeddings)
            tokens = np.zeros((self.batch_size, b), np.int32)
            mask = np.zeros((self.batch_size, b), np.int32)
            mask[:, 0] = 1
            with jax.set_mesh(self.mesh):
                self._embed(self.params, jnp.asarray(tokens), jnp.asarray(mask))

    def embed_batch(self, prompts: list[np.ndarray]) -> np.ndarray:
        """Embed N token sequences -> [N, H] f32 unit vectors."""
        if not prompts:
            return np.zeros((0, self.cfg.hidden_size), np.float32)
        max_pos = self.cfg.max_position_embeddings
        out = np.empty((len(prompts), self.cfg.hidden_size), np.float32)
        order = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
        for start in range(0, len(order), self.batch_size):
            idx = order[start:start + self.batch_size]
            longest = max(len(prompts[i]) for i in idx)
            if longest > max_pos:
                raise ValueError(
                    f"sequence length {longest} exceeds the encoder's "
                    f"max_position_embeddings {max_pos}"
                )
            b = bucket_length(longest, max_pos)
            tokens = np.zeros((self.batch_size, b), np.int32)
            mask = np.zeros((self.batch_size, b), np.int32)
            for row, i in enumerate(idx):
                p = np.asarray(prompts[i], np.int32)
                tokens[row, : p.size] = p
                mask[row, : p.size] = 1
            # Fully padded rows still flow through softmax: give them one
            # live position so the bias row isn't all -inf.
            for row in range(len(idx), self.batch_size):
                mask[row, 0] = 1
            with jax.set_mesh(self.mesh):
                vecs = np.asarray(
                    self._embed(self.params, jnp.asarray(tokens), jnp.asarray(mask))
                )
            for row, i in enumerate(idx):
                out[i] = vecs[row]
        return out
