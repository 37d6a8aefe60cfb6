"""Attention ops: XLA-fused reference paths and the Pallas dispatch.

Grouped-query attention (GQA) with a position-based mask, which uniformly
covers:
  - full causal self-attention (prefill / training),
  - decode-against-cache (each query attends to cache slots with
    key_position <= query_position and slot < used length).

The reference path is plain einsum + softmax: XLA fuses this well on TPU and
keeps the matmuls on the MXU. Two Pallas kernels take over where reading
less is the point. The flash kernel (:mod:`kukeon_tpu.ops.flash_attention`)
serves long-sequence prefill and training on TPU, where materializing the
[S, S] score matrix would blow HBM bandwidth. The decode kernel
(:mod:`kukeon_tpu.ops.decode_attention`) serves ``decode_gqa_attention`` on
one TPU with a full-precision cache: a decode step is bound by the cache
bytes it streams, the XLA body streams every row of every slot whatever is
live, and the kernel fetches only the blocks that hold live rows.
"""

import jax
import jax.numpy as jnp

from kukeon_tpu.ops import dispatch

NEG_INF = -1e30


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for GQA: [B, S, KV, D] -> [B, S, KV * n_rep, D]."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d))
    return x.reshape(b, s, kv * n_rep, d)


def attention_mask(
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    kv_length: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Boolean mask [B, 1, Sq, Skv]: True = attend.

    Args:
      q_positions: [B, Sq] absolute positions of the queries.
      kv_positions: [B, Skv] absolute positions of the keys.
      kv_length: optional [B] number of valid cache slots; slots at index >=
        kv_length are masked out (used when attending to a fixed-size cache).
    """
    causal = kv_positions[:, None, :] <= q_positions[:, :, None]  # [B, Sq, Skv]
    if kv_length is not None:
        skv = kv_positions.shape[-1]
        valid = jnp.arange(skv)[None, None, :] < kv_length[:, None, None]
        causal = jnp.logical_and(causal, valid)
    return causal[:, None, :, :]


def attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """Masked multi-head attention via einsum (GQA-expanded inputs).

    Args:
      q: [B, Sq, H, D]; k, v: [B, Skv, H, D]; mask: [B, 1, Sq, Skv] bool.

    Returns:
      [B, Sq, H, D] in q's dtype. Softmax is computed in float32.
    """
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def attention_grouped(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """GQA attention WITHOUT materializing repeated KV heads.

    ``repeat_kv`` + reference attention reads (and copies) the KV tensors
    ``n_heads/n_kv`` times — for a decode step against a large cache that
    multiplies the dominant HBM stream by the group factor. Grouping the
    query heads instead ([B, Sq, KV, G, D]) keeps every KV byte read exactly
    once; same math, same mask semantics.

    Args:
      q: [B, Sq, H, D]; k, v: [B, Skv, KV, D] (H % KV == 0);
      mask: [B, 1, Sq, Skv] bool.
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qg = q.reshape(B, Sq, KV, G, D)
    scores = jnp.einsum(
        "bqkgd,bTkd->bkgqT", qg, k, preferred_element_type=jnp.float32
    ) * scale
    # mask [B, 1, Sq, Skv] -> broadcast over (KV, G).
    scores = jnp.where(mask[:, :, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqT,bTkd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def blocked_attention(q, k, v, window: int | None, block: int,
                      scale: float | None = None):
    """Causal GQA over one prompt in query blocks of ``block`` rows. A block
    of a window layer slices the band of keys it can see; a full layer's
    block, the keys up to its last row. q [B, S, NH, D]; k, v [B, S, KV, D].
    ``scale`` multiplies the float32 scores; None: ``D ** -0.5``."""
    B, S, NH, D = q.shape
    KV = k.shape[2]
    block = min(block, S)
    if S % block:
        raise ValueError(f"a prompt bucket of {S} rows is no multiple of the "
                         f"query block {block}")
    if scale is None:
        scale = D ** -0.5
    outs = []
    for q0 in range(0, S, block):
        q1 = q0 + block
        k0 = 0 if window is None else max(0, q0 - window)
        qb = q[:, q0:q1].reshape(B, block, KV, NH // KV, D)
        s = jnp.einsum("bqkgd,bTkd->bkgqT", qb, k[:, k0:q1],
                       preferred_element_type=jnp.float32) * scale
        back = (q0 + jnp.arange(block))[:, None] - (k0 + jnp.arange(q1 - k0))
        see = back >= 0
        if window is not None:
            see &= back < window
        p = jax.nn.softmax(jnp.where(see, s, NEG_INF), axis=-1)
        o = jnp.einsum("bkgqT,bTkd->bqkgd", p.astype(v.dtype), v[:, k0:q1])
        outs.append(o.reshape(B, block, NH, D))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def decode_block_rows(heads: int, kv_heads: int, rows: int, head_dim: int,
                      dtype, cache_dtype, devices: int) -> int | None:
    """The rows of the blocks in which ``decode_gqa_attention`` reads a
    cache of ``rows`` rows a slot, or None where its XLA body reads every
    row. The kernel runs where the call can observe all of: a TPU; a
    full-precision cache in the activations' dtype (an int8 cache and its
    scales are the XLA body's); one device (GSPMD does not partition a
    ``pallas_call``; a tensor axis needs its ``shard_map`` over the KV heads
    first); shapes the kernel's blocks tile. The engine asks the same
    question of its own shapes to count the rows a chunk reads."""
    from kukeon_tpu.ops import decode_attention as da

    if (jax.default_backend() != "tpu" or devices > 1
            or jnp.dtype(cache_dtype) != jnp.dtype(dtype)
            or not da.supports(heads, kv_heads, rows, head_dim, cache_dtype)):
        return None
    return da.block_rows(kv_heads, rows, head_dim, cache_dtype)


@jax.named_scope("attention")
def decode_gqa_attention(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    layer: jnp.ndarray,
    lengths: jnp.ndarray,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    skip: jnp.ndarray | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Single-token decode attention against a cache, append-free.

    The new token's K/V are NOT written into the cache first (that write
    pattern forces a full-cache copy per layer inside a scan); instead the
    cache contributes its first ``lengths`` rows and the current token
    contributes one extra score, softmaxed together. The caller inserts the
    new K/V into the cache once per step, outside the layer scan.

    Two bodies, chosen by ``decode_block_rows``. On one TPU with a
    full-precision cache the Pallas kernel
    (:mod:`kukeon_tpu.ops.decode_attention`) walks each slot's rows block by
    block and stops at its last live block: a slot of ``lengths`` 0 (the
    caller passes 0 where a slot is not active) moves no cache byte.
    Everywhere else the XLA body below scores every row and masks; it is
    also the kernel's reference in the tests.

    Quantized cache: cache_k/cache_v int8 with per-token per-head scales
    k_scale/v_scale [layers, B, S, KV]. Dequant is fused: the score dot runs on the
    int8 keys (convert folds into the einsum, so int8 is the HBM stream) and
    the per-token key scale multiplies the f32 scores; the value scale folds
    into the probabilities before the value dot. Exact same math as
    dequantize-then-attend, at half the cache bytes.

    Args:
      q: [B, 1, H, D]; k_new, v_new: [B, 1, KV, D] (always full precision);
      cache_k, cache_v: the stack [layers, B, S, KV, D], read at index
        ``layer`` (a traced scalar inside a scan over layers). The whole
        stack and not the layer's slice, because the kernel reads a layer
        of its operand in place where a slice would be copied out first;
      lengths: [B] int32 rows to attend, ``row < lengths`` (at most S);
      skip: optional [B] int32, one row below ``lengths`` left out (a ring
        that holds a window's rows: the row the new token is about to take;
        ``kv_kinds.valid``). A row at or past ``lengths`` excludes nothing.
      scale: what multiplies the float32 scores, in either body; None:
        ``D ** -0.5`` (a model whose published multiplier is another says so).

    Returns: [B, 1, H, D].
    """
    if decode_block_rows(
            q.shape[2], cache_k.shape[-2], cache_k.shape[-3], q.shape[3],
            q.dtype, cache_k.dtype,
            jax.sharding.get_abstract_mesh().size) is not None:
        from kukeon_tpu.ops import decode_attention as da

        dispatch.note("decode_gqa_attention", "pallas")
        # The engine holds the stack [layers, B, KV, S, D] and the scan
        # carries this function's view of it: swapping back is a relabelling
        # of the held bytes, which the kernel's operand (default layout) is.
        return da.decode_attention(
            q, k_new, v_new, jnp.swapaxes(cache_k, 2, 3),
            jnp.swapaxes(cache_v, 2, 3), lengths, skip, layer, scale=scale)
    dispatch.note("decode_gqa_attention", "xla")
    cache_k, cache_v, k_scale, v_scale = (
        None if x is None else
        jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)
        for x in (cache_k, cache_v, k_scale, v_scale))
    B, _, H, D = q.shape
    S = cache_k.shape[1]
    KV = cache_k.shape[2]
    G = H // KV
    scale = (1.0 / jnp.sqrt(D).astype(jnp.float32) if scale is None
             else jnp.float32(scale))
    dt = q.dtype

    qg = q.reshape(B, KV, G, D)
    # Only the quantized path converts (int8 -> activation dtype folds into
    # the dot); a full-precision cache keeps its own dtype so callers with a
    # wider-than-activations cache lose nothing.
    ck = cache_k.astype(dt) if k_scale is not None else cache_k
    s_cache = jnp.einsum(
        "bkgd,bTkd->bkgT", qg, ck, preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        s_cache = s_cache * k_scale.transpose(0, 2, 1)[:, :, None, :]
    row = jnp.arange(S)[None, :]
    valid = row < lengths[:, None]
    if skip is not None:
        valid = valid & (row != skip[:, None])
    s_cache = jnp.where(valid[:, None, None, :], s_cache, NEG_INF)
    s_self = jnp.einsum(
        "bkgd,bkd->bkg", qg, k_new.reshape(B, KV, D),
        preferred_element_type=jnp.float32,
    )[..., None] * scale

    probs = jax.nn.softmax(jnp.concatenate([s_cache, s_self], axis=-1), axis=-1)
    p_cache = probs[..., :S]
    if v_scale is not None:
        p_cache = p_cache * v_scale.transpose(0, 2, 1)[:, :, None, :]
        cv = cache_v.astype(dt)
        p_cache = p_cache.astype(dt)
    else:
        cv = cache_v
        p_cache = p_cache.astype(cache_v.dtype)
    p_self = probs[..., S:].astype(v_new.dtype)
    out = (
        jnp.einsum("bkgT,bTkd->bkgd", p_cache, cv)
        + p_self * v_new.reshape(B, KV, 1, D)
    )
    return out.reshape(B, 1, H, D).astype(q.dtype)


@jax.named_scope("attention")
def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    kv_length: jnp.ndarray | None = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """GQA attention entry point used by the model.

    q: [B, Sq, NH, D]; k, v: [B, Skv, NKV, D] with NH % NKV == 0.

    ``impl``: "auto" picks flash on TPU for long-enough sequences, else the
    XLA reference; "reference" / "flash" / "ring" / "ulysses" force a path.
    "ring" (shard_map + ppermute) and "ulysses" (all-to-all seq<->heads) are
    the sequence-parallel paths over the ``seq`` mesh axis and require an
    ambient mesh (``jax.set_mesh``) with one.
    """
    if impl in ("ring", "ulysses"):
        if kv_length is not None or q.shape[1] != k.shape[1]:
            raise ValueError(
                f"impl={impl!r} requires full self-attention (Sq == Skv, no "
                f"kv_length); got Sq={q.shape[1]}, Skv={k.shape[1]}, "
                f"kv_length={'set' if kv_length is not None else 'None'}. "
                "Use 'reference' or 'auto' for cached decode."
            )
        if impl == "ulysses":
            from kukeon_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention(
                q, k, v, q_positions=q_positions, kv_positions=kv_positions
            )
        from kukeon_tpu.parallel.ring_attention import ring_attention

        return ring_attention(
            q, k, v, q_positions=q_positions, kv_positions=kv_positions
        )

    n_heads = q.shape[2]
    n_kv = k.shape[2]

    from kukeon_tpu.ops import flash_attention as fa

    use_flash = False
    if impl == "flash":
        if kv_length is not None or not fa.supports(q.shape[1], k.shape[1]):
            raise ValueError(
                "impl='flash' requires full self-attention with Sq == Skv, "
                "Sq >= 128, Sq a multiple of the 256 block, and no kv_length; "
                f"got Sq={q.shape[1]}, Skv={k.shape[1]}, "
                f"kv_length={'set' if kv_length is not None else 'None'}. "
                "Use 'reference' or 'auto'."
            )
        use_flash = True
    elif impl == "auto":
        # Flash pays off when the score matrix is big; decode (Sq==1), tiny
        # prefills, cache attention, and non-TPU backends stay on the fused
        # XLA path.
        # Measured on v5e: parity at S=2048, 27x at S=8192 (the XLA path
        # materializes the [S, S] scores); flash also saves the O(S^2) HBM.
        use_flash = (
            kv_length is None
            and q.shape[1] >= 1024
            and fa.supports(q.shape[1], k.shape[1])
            and jax.default_backend() == "tpu"
        )

    dispatch.note("gqa_attention", "pallas" if use_flash else "xla")
    if use_flash:
        k = repeat_kv(k, n_heads // n_kv)
        v = repeat_kv(v, n_heads // n_kv)
        return fa.flash_attention(q, k, v, q_positions, kv_positions)

    # XLA path: grouped-query einsum — KV is never head-repeated, so cache
    # bytes stream through HBM exactly once.
    mask = attention_mask(q_positions, kv_positions, kv_length)
    return attention_grouped(q, k, v, mask)
