"""Token sampling: greedy, temperature, top-k, top-p — all jit-friendly."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0   # 0 => greedy
    top_k: int = 0             # 0 => disabled
    top_p: float = 1.0         # 1 => disabled
    max_new_tokens: int = 256
    # Per-request stop tokens (host-side check in the engine's emit path —
    # the slot frees the moment one is generated; the stop token itself is
    # included in the output, clients strip it if unwanted).
    stop_tokens: tuple[int, ...] = ()


def slot_sampling_arrays(
    slot_requests, num_slots: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-slot (temps, top_ks, top_ps) host arrays for
    :func:`sample_per_slot`, from (slot, request) pairs whose requests carry
    a :class:`SamplingParams`. Empty slots sample greedily (temp 0), which
    is also a no-op for inactive slots in the decode program."""
    temps = np.zeros((num_slots,), np.float32)
    top_ks = np.zeros((num_slots,), np.int32)
    top_ps = np.ones((num_slots,), np.float32)
    for slot, req in slot_requests:
        sp = req.sampling
        temps[slot] = sp.temperature
        top_ks[slot] = sp.top_k
        top_ps[slot] = sp.top_p
    return temps, top_ks, top_ps


def sample(logits: jnp.ndarray, key: jax.Array, params: SamplingParams) -> jnp.ndarray:
    """Sample next tokens from [B, V] logits -> [B] int32.

    All branches are trace-time (params is static), so each SamplingParams
    value compiles one specialization.
    """
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    logits = logits / params.temperature

    if params.top_k > 0:
        top_vals, _ = jax.lax.top_k(logits, params.top_k)
        kth = top_vals[:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)

    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative mass >= top_p (always keep 1).
        cutoff_idx = jnp.sum(cum < params.top_p, axis=-1)
        cutoff_logit = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff_logit, -jnp.inf, logits)

    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@jax.named_scope("sample")
def sample_per_slot(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Vectorized sampling with *dynamic per-slot* parameters.

    One compiled program covers any mix of greedy/temperature/top-k/top-p
    across the batch — the serving engine's decode path uses this so slot
    composition never recompiles.

    Args:
      logits: [B, V] float32.
      temperature: [B]; <= 0 means greedy for that slot.
      top_k: [B] int32; 0 disables.
      top_p: [B]; >= 1 disables.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / t

    def filter_topk_topp(scaled):
        # top-k: mask logits below the k-th largest (k==0 -> keep all).
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        kk = jnp.clip(top_k, 1, V) - 1
        kth = jnp.take_along_axis(sorted_desc, kk[:, None], axis=-1)
        scaled = jnp.where((top_k > 0)[:, None] & (scaled < kth), -jnp.inf, scaled)

        # top-p on the (re-sorted) top-k-filtered distribution: smallest
        # prefix with mass >= top_p (matches the static ``sample`` semantics).
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs_sorted, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
        cutoff_logit = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None], axis=-1)
        apply_p = (top_p < 1.0)[:, None]
        return jnp.where(apply_p & (scaled < cutoff_logit), -jnp.inf, scaled)

    # The sorts are expensive over a 128k vocab; skip them at runtime unless
    # some slot actually uses top-k/top-p.
    needs_filter = jnp.any(top_k > 0) | jnp.any(top_p < 1.0)
    scaled = jax.lax.cond(needs_filter, filter_topk_topp, lambda s: s, scaled)

    # Categorical = gumbel noise over the whole [B, V] block (an RNG sweep
    # per decode step) — skip it too when every slot is greedy.
    any_stochastic = jnp.any(temperature > 0)
    sampled = jax.lax.cond(
        any_stochastic,
        lambda s: jax.random.categorical(key, s, axis=-1).astype(jnp.int32),
        lambda s: greedy,
        scaled,
    )
    return jnp.where(temperature > 0, sampled, greedy)
