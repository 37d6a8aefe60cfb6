"""The sparse_latent_moe family (models/sparse_latent_moe.py,
ops/sparse_attention.py, the named-array row kind of models/kv_kinds.py, the
group-limited ``route`` of models/expert_layer.py) on the CPU at its tiny
preset: a selection of 8 rows, contexts of 20-100, against the benchmark's
plain reference (logits, not tokens)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import expert_layer as el
from kukeon_tpu.models import families, kv_kinds
from kukeon_tpu.models import sparse_latent_moe as slm
from kukeon_tpu.ops import rope
from kukeon_tpu.ops import sparse_attention as sa
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

SEED = 5
ROWS = 128


def reference_config(cfg: slm.SparseLatentMoEConfig) -> dict:
    """The keys ``benchmark/reference/sparse_latent_moe.py`` reads, for a
    program config (what ``benchmark/launchers/sparse_latent_moe.py`` maps the
    other way)."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "router_experts": cfg.num_experts,
        "experts_held": list(cfg.experts_held),
        "num_experts_per_tok": cfg.experts_per_token,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "factor": cfg.rope_factor, "mscale": cfg.rope_mscale,
            "original_max_position_embeddings": cfg.rope_original_max,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow},
        "max_position_embeddings": cfg.max_seq_len,
        "routed_scaling_factor": cfg.route_scale,
        "norm_topk_prob": cfg.route_norm,
        "torch_dtype": jnp.dtype(cfg.dtype).name}


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(slm.sparse_latent_moe_tiny(), max_seq_len=ROWS)
    return cfg, slm.init_params(jax.random.key(SEED), cfg)


@pytest.fixture(scope="module")
def reference():
    return plugins.load("reference", "sparse_latent_moe")


@pytest.fixture(scope="module")
def tokens(tiny):
    return np.random.default_rng(0).integers(
        0, tiny[0].vocab_size, 100).astype(np.int32)


def _padded(seq, n, bucket):
    out = np.zeros((1, bucket), np.int32)
    out[0, :n] = seq[:n]
    return jnp.asarray(out)


def _empty_cache(cfg, kinds, slots):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        kv_kinds.shapes(kinds, slots, cfg.num_kv_heads, cfg.head_dim,
                        cfg.dtype))


# --- the model against the reference ------------------------------------------

@pytest.mark.parametrize("n, bucket", [(3, 16), (8, 16), (9, 16), (33, 64),
                                       (70, 128)])
def test_a_right_padded_prefill_gives_the_reference_logits_at_its_length(
        tiny, reference, tokens, n, bucket):
    """Prompts under, at and past the selection of 8 rows, in buckets that
    pad them: the padding takes no part in any real row's selection."""
    cfg, params = tiny
    logits, block, counters = slm.prefill(params, cfg,
                                          _padded(tokens, n, bucket), n)
    want = reference.logits_at(reference_config(cfg), SEED, [tokens[:n]],
                               [np.array([n - 1])], ROWS)[0][0]
    assert np.abs(np.asarray(logits) - want).max() < 2e-4
    assert {k: v.shape for k, v in block.items()} == {
        "ckv": (3, 1, bucket, 128), "kidx": (3, 1, bucket, 16)}
    (routed, hits, held, reached, pair_rows, worked, routed_tokens, selected,
     live) = np.asarray(counters)
    # two expert layers of 4 held experts, one piece each: the bucket's rows
    # make top-4 pairs, one block of them, walked where a pair is held
    assert held == 2 * 4 and 0 < reached <= held
    assert pair_rows == 2 * bucket * 4 and hits <= worked <= pair_rows
    assert worked % (bucket * 4) == 0
    assert routed_tokens == 2 * n and routed == 2 * n * 4
    assert 0 < hits < routed and selected == live == 0


def test_prefill_then_decode_through_the_cache_matches_the_full_forward(
        tiny, reference, tokens):
    """70 tokens prefilled into slot 1 of two, then 29 decode steps through
    ``kv_kinds.insert`` / ``append``: the absorbed form over the gathered
    rows against the reference's expanded full forward at every step, the
    other slot idle."""
    cfg, params = tiny
    P = 70
    want = reference.logits_at(reference_config(cfg), SEED, [tokens[:99]],
                               [np.arange(P - 1, 98)], ROWS)[0]
    kinds = cfg.cache_kinds(ROWS)
    logits, block, _ = slm.prefill(params, cfg, _padded(tokens, P, ROWS), P)
    assert np.abs(np.asarray(logits) - want[0]).max() < 2e-4
    cache = kv_kinds.insert(_empty_cache(cfg, kinds, 2), kinds, block, P, 1)
    active = jnp.array([False, True])
    step = jax.jit(lambda t, c: slm.decode(params, cfg, t, c, kinds, active))
    worst = 0.0
    for n in range(P, 98):
        lg, new, counters = step(jnp.array([0, tokens[n]], jnp.int32), cache)
        cache = kv_kinds.append(cache, kinds, new, active)
        worst = max(worst, float(np.abs(np.asarray(lg[1])
                                        - want[n - P + 1]).max()))
        *_moe, routed_tokens, selected, live = np.asarray(counters)
        # one active slot: 8 of its n + 1 positions a layer
        assert (routed_tokens, selected, live) == (2, 3 * 8, 3 * (n + 1))
    assert worst < 2e-4
    assert cache.lengths.tolist() == [0, 98]


def test_the_absorbed_decode_is_the_expanded_prefill_one_token_on(tiny, tokens):
    """The two forms of one attention, both the program's: a decode step at
    position n (q_nope through Wkv_b^K against the latent, the mix through
    Wkv_b^V, over the gathered selection) gives the logits a prefill of n + 1
    tokens gives at its last row (K and V expanded, the selection a mask)."""
    cfg, params = tiny
    kinds = cfg.cache_kinds(ROWS)
    for n in (5, 8, 40):
        _, block, _ = slm.prefill(params, cfg, _padded(tokens, n, 64), n)
        cache = kv_kinds.insert(_empty_cache(cfg, kinds, 1), kinds, block, n, 0)
        lg, _, _ = slm.decode(params, cfg, jnp.asarray(tokens[n:n + 1]), cache,
                              kinds, jnp.array([True]))
        want, _, _ = slm.prefill(params, cfg, _padded(tokens, n + 1, 64), n + 1)
        assert float(jnp.abs(lg[0] - want).max()) < 2e-4


def test_the_engine_serves_two_slots_past_the_selection(tiny, reference):
    """ServingEngine's own prefill, insert and decode_chunk: two requests of
    12 and 40 tokens decode side by side; every served token is the
    reference's best at its position, the state holds the two named arrays,
    and the counters say what a step read."""
    cfg, params = tiny
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=ROWS,
                        decode_chunk=4, prefill_buckets=(16, 32, 64, 128))
    held, = eng.state.cache.held
    assert {k: v.shape for k, v in held.items()} == {
        "kidx": (3, 2, 128, 16), "ckv": (3, 2, 128, 128)}
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 12),
               rng.integers(0, cfg.vocab_size, 40)]
    reqs = [eng.submit(prompts[0], SamplingParams(max_new_tokens=30)),
            eng.submit(prompts[1], SamplingParams(max_new_tokens=21))]
    rows = {}
    while not all(r.done.is_set() for r in reqs):
        eng.step()
        rows = {s[0]["kind"]: s[1] for fam in eng._obs_collect()
                if fam[0] == "kukeon_engine_kv_rows" for s in fam[3]} \
            if eng._active_requests() else rows
    assert 0 < rows["latent"] <= 12 + 30 + 40 + 21
    for prompt, req in zip(prompts, reqs):
        seq = np.concatenate([prompt, req.generated])
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        logits = reference.logits_at(reference_config(cfg), SEED, [seq],
                                     [pos], ROWS)[0]
        gaps = logits.max(-1) - logits[np.arange(len(pos)), seq[pos + 1]]
        assert gaps.max() < 1e-4
    value = lambda name, **kw: eng.registry.get(name).value(**kw)  # noqa: E731
    tokens_routed = value("kukeon_moe_routed_tokens_total")
    assert tokens_routed >= 2 * (12 + 40 + 29 + 20)
    assert 0 < value("kukeon_moe_held_hits_total") < 4 * tokens_routed
    assert value("kukeon_moe_routed_total") == 4 * tokens_routed
    selected = value("kukeon_sparse_rows_selected_total")
    live = value("kukeon_sparse_rows_live_total")
    # every decode step had more than 8 positions before it: 8 a layer read
    assert 0 < selected < live and selected % (3 * 8) == 0
    held_rows = value("kukeon_engine_decode_kv_rows_total", what="held")
    read_rows = value("kukeon_engine_decode_kv_rows_total", what="read")
    assert 0 < read_rows < held_rows


# --- the cache kind -------------------------------------------------------------

def test_a_row_of_named_arrays_is_inserted_appended_and_read_by_its_widths():
    kd = kv_kinds.CacheKind("latent", (0, 1), 16,
                            arrays=(("kidx", 4), ("ckv", 12)), select=5)
    kinds = (kd,)
    assert kd.unit == "rows" and kd.row_names() == ("kidx", "ckv")
    assert kv_kinds.names(kinds) == ("ckv", "kidx")
    assert (kd.live(3), kd.live(40)) == (3, 16)
    # every array of every live row is fetched; 5 of them are attended
    assert (kd.read(3), kd.read(10), kd.read(40)) == (3, 10, 16)
    shapes = kv_kinds.shapes(kinds, 3, 2, 8, jnp.float32)
    assert {k: v.shape for k, v in shapes.held[0].items()} == {
        "kidx": (2, 3, 16, 4), "ckv": (2, 3, 16, 12)}
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    assert kv_kinds.view(cache).held[0]["ckv"].shape == (2, 3, 16, 12)
    rng = np.random.default_rng(0)
    block = {"kidx": jnp.asarray(rng.normal(size=(2, 1, 8, 4)), jnp.float32),
             "ckv": jnp.asarray(rng.normal(size=(2, 1, 8, 12)), jnp.float32)}
    cache = kv_kinds.insert(cache, kinds, block, 6, 2)
    assert cache.lengths.tolist() == [0, 0, 6]
    for name in ("kidx", "ckv"):
        got = np.asarray(cache.held[0][name])
        np.testing.assert_array_equal(got[:, 2, :8], block[name][:, 0])
        assert not got[:, :2].any() and not got[:, 2, 8:].any()
    new = {"kidx": jnp.ones((2, 3, 1, 4)), "ckv": 2 * jnp.ones((2, 3, 1, 12))}
    active = jnp.array([True, False, True])
    after = kv_kinds.append(cache, kinds, new, active)
    assert after.lengths.tolist() == [1, 0, 7]
    got = np.asarray(after.held[0]["ckv"])
    assert (got[:, 0, 0] == 2).all() and (got[:, 2, 6] == 2).all()
    np.testing.assert_array_equal(got[:, 2, :6], block["ckv"][:, 0, :6])
    # a full stack: a step reads the rows below its length, none left out
    rows, skip = kv_kinds.valid(kd, after.lengths)
    assert rows.tolist() == [1, 0, 7] and skip is None


def test_the_served_state_holds_a_latent_row_and_an_index_key_a_token():
    """At the benchmark's cut (shapes only, nothing is allocated): 16 slots
    of 32768 rows, a row the 576 latent values in 640 lanes and 128 of the
    index key over five layers; 9.29 GB of weights."""
    cfg = dataclasses.replace(
        slm.deepseek_v32_exp(), num_layers=5, num_dense_layers=1,
        experts_held=(0, 16), vocab_size=16160, max_seq_len=32768)
    kind, = cfg.cache_kinds(32768)
    assert (kind.name, kind.rows, kind.select, kind.ring) == (
        "latent", 32768, 2048, False)
    shapes = kv_kinds.shapes((kind,), 16, cfg.num_kv_heads, cfg.head_dim,
                             cfg.dtype)
    assert {k: v.shape for k, v in shapes.held[0].items()} == {
        "kidx": (5, 16, 32768, 128), "ckv": (5, 16, 32768, 640)}
    held = sum(np.prod(s.shape) * 2 for s in shapes.held[0].values())
    assert held == 16 * 32768 * 5 * (640 + 128) * 2         # 4.03 GB
    params = jax.eval_shape(lambda k: slm.init_params(k, cfg),
                            jax.random.key(0))
    weights = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 9.25e9 < weights < 9.30e9
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)


# --- the selection and the attention under it ---------------------------------------

def _index_inputs(Q, S, Hh=4, D=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (Q, Hh, D)),
            jax.random.uniform(ks[1], (Q, Hh), minval=-0.2, maxval=1.0),
            jax.random.normal(ks[2], (S, D)))


def _mask_of(tiles):
    nk, Q, T = tiles.shape
    return np.asarray(tiles).transpose(1, 0, 2).reshape(Q, nk * T) != 0


@pytest.mark.parametrize("row0, topk", [(0, 8), (32, 8), (96, 200)])
def test_the_selection_is_the_topk_of_a_loop_written_out(row0, topk):
    """``select_rows`` (the XLA body and the kernel in interpret mode) against
    index scores and a sort written out row by row: a row under ``topk``
    positions keeps every one it may see, a longer one its ``topk`` best (and
    what ties with the last of them)."""
    Q, S = 32, 128
    q, w, k = _index_inputs(Q, S)
    want = np.zeros((Q, S), bool)
    for i in range(Q):
        t = row0 + i
        score = sum(float(w[i, j]) * np.maximum(
            np.asarray(q[i, j]) @ np.asarray(k[:t + 1]).T, 0.0)
            for j in range(q.shape[1]))
        # positions that tie with the topk-th (every head's product under
        # the relu: a score of exactly 0) are kept with it
        want[i, :t + 1] = score >= np.sort(score)[::-1][min(topk, t + 1) - 1]
    got = _mask_of(sa.select_rows(q, w, k, jnp.int32(row0), topk=topk))
    np.testing.assert_array_equal(got, want)
    kernel = _mask_of(sa.select_rows(q, w, k, jnp.int32(row0), topk=topk,
                                     interpret=True))
    np.testing.assert_array_equal(kernel, want)


def test_the_masked_prefill_is_attention_over_a_gather_of_the_selected_rows():
    """``masked_attention`` (XLA body and the kernel in interpret mode)
    against, for each query, a softmax over ONLY the rows its selection
    names, gathered out: the mask leaves nothing of the others in."""
    G, Q, S, Dk, Dv, row0 = 2, 32, 64, 24, 16, 32
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (G, Q, Dk))
    k = jax.random.normal(ks[1], (G, S, Dk))
    v = jax.random.normal(ks[2], (G, S, Dv))
    qi, w, ki = _index_inputs(Q, S, seed=4)
    tiles = sa.select_rows(qi, w, ki, jnp.int32(row0), topk=8)
    mask = _mask_of(tiles)
    assert (mask.sum(-1) == 8).all()
    want = np.zeros((G, Q, Dv), np.float32)
    for i in range(Q):
        rows = np.nonzero(mask[i])[0]
        assert rows.max() <= row0 + i
        s = np.einsum("gd,gkd->gk", q[:, i], k[:, rows]) * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want[:, i] = np.einsum("gk,gkd->gd", p / p.sum(-1, keepdims=True),
                               v[:, rows])
    for interpret in (None, True):
        got = sa.masked_attention(q, k, v, tiles, jnp.int32(row0), scale=0.2,
                                  interpret=interpret)
        np.testing.assert_allclose(got, want, atol=2e-5)


def _attend_loop(q, new, own, scores, latents, lengths, topk, scale, R):
    """``sa.decode_attention`` a slot at a time, in numpy: the live scores and
    the step's own sorted, everything at or above the topk-th kept, a softmax
    over exactly those rows' products."""
    out = np.zeros((*q.shape[:2], R), np.float32)
    kept = []
    for b in range(q.shape[0]):
        n = int(lengths[b])
        both = np.append(np.asarray(scores[b, :n], np.float32), float(own[b]))
        kth = np.sort(both)[::-1][min(topk, n + 1) - 1]
        named = np.nonzero(both >= kth)[0]
        rows = np.stack([np.asarray(new[b] if r == n else latents[b, r],
                                    np.float32) for r in named])
        s = np.asarray(q[b], np.float32) @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = p / p.sum(-1, keepdims=True) @ rows[:, :R]
        kept.append(len(named))
    return out, kept


def test_the_decode_indexer_scores_the_live_rows_and_the_step_joins_them():
    """``decode_index_scores`` reads a layer of the held stack up to each
    slot's length (XLA body and kernel alike); ``decode_attention`` ranks the
    step's own position with them and attends exactly the rows at or above
    the topk-th score, the step's own among them without having been
    written."""
    B, Hh, D, rows, W, R = 3, 4, 16, 64, 32, 24
    ks = jax.random.split(jax.random.key(6), 6)
    q = jax.random.normal(ks[0], (B, Hh, D))
    w = jax.random.uniform(ks[1], (B, Hh))
    keys = jax.random.normal(ks[2], (2, B, rows, D))
    lengths = jnp.array([0, 5, 40])
    want = np.full((B, rows), -np.inf, np.float32)
    for b in range(B):
        n = int(lengths[b])
        s = np.einsum("hd,kd->hk", q[b], keys[1, b, :n])
        want[b, :n] = np.einsum("hk,h->k", np.maximum(s, 0), w[b])
    for interpret in (None, True):
        got = sa.decode_index_scores(q, w, keys, jnp.int32(1), lengths,
                                     interpret=interpret)
        np.testing.assert_allclose(got, want, atol=1e-5)
    own = jnp.array([0.5, -1.0, 1e9])       # slot 2's own row is its best
    latents = jax.random.normal(ks[3], (2, B, rows, W))
    new = jax.random.normal(ks[4], (B, W))
    qa = jax.random.normal(ks[5], (B, 5, W))
    mix, kept = _attend_loop(qa, new, own, want, latents[1], lengths, 8, 0.3,
                             R)
    assert kept == [1, 6, 8]
    for interpret in (None, True):
        got, n = sa.decode_attention(
            qa, new, own, jnp.asarray(want), latents, jnp.int32(1), lengths,
            topk=8, scale=0.3, value_dim=R, interpret=interpret)
        assert n.tolist() == kept
        np.testing.assert_allclose(got, mix, atol=2e-5)
    # a slot that holds nothing attends to its own row alone
    np.testing.assert_allclose(got[0], np.broadcast_to(new[0, :R], (5, R)),
                               atol=1e-6)


@pytest.mark.parametrize("case, length, own, kept", [
    ("not active", 0, 0.0, 1),
    ("fewer live rows than topk", 5, 0.0, 6),
    ("own row makes topk", 7, 0.0, 8),
    ("own row the best", 50, 99.0, 8),
    ("own row the worst", 50, -99.0, 8),
    ("a length inside a block", 21, 0.25, 8),
    ("a tie at the boundary", 40, 0.0, 10),
    ("every row lives", 64, 0.0, 8),
])
def test_the_selecting_decode_is_the_loop_written_out(case, length, own, kept):
    """``decode_attention``: the kernel (interpret mode, blocks of 8 rows so
    that a slot's rows span several and end inside one) against its XLA body
    against ``_attend_loop``. One rule: everything at or above the topk-th of
    the live scores and the step's own, never a row at or past the length;
    rows that tie with the topk-th are all kept."""
    B, NH, rows, W, R, topk = 2, 5, 64, 32, 24, 8
    ks = jax.random.split(jax.random.key(length), 5)
    q = jax.random.normal(ks[0], (B, NH, W))
    new = jax.random.normal(ks[1], (B, W))
    latents = jax.random.normal(ks[2], (3, B, rows, W))
    scores = np.array(jax.random.normal(ks[3], (B, rows)))
    if case == "a tie at the boundary":
        # three live rows share the 8th place of slot 1; so does a row past
        # the length, which no rule may keep
        order = np.argsort(scores[1, :length])[::-1]
        scores[1, order[7:10]] = scores[1, order[7]]
        scores[1, length + 3] = scores[1, order[7]]
    # slot 0 stands beside the case: a live slot with a score of its own
    lengths = jnp.array([33, length])
    owns = jnp.array([0.1, own], jnp.float32)
    mix, n = _attend_loop(q, new, owns, scores, latents[2], lengths, topk, 0.2,
                          R)
    assert n == [8, kept]
    for interpret in (None, True):
        got, count = sa.decode_attention(
            q, new, owns, jnp.asarray(scores), latents, jnp.int32(2), lengths,
            topk=topk, scale=0.2, value_dim=R, interpret=interpret)
        assert count.tolist() == n
        np.testing.assert_allclose(got, mix, atol=2e-5)


def test_yarn_keeps_fast_pairs_and_stretches_slow_ones():
    """dim 64, theta 10000, factor 40 over 4096 trained positions: the pairs
    that turn more than 32 times in 4096 positions keep their frequency, the
    ones that turn less than once are divided by 40, a ramp between; the
    softmax takes (0.1 ln 40 + 1) ** 2."""
    plain = np.asarray(rope.rope_frequencies(64, 10000.0))
    got = np.asarray(rope.yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    turns = plain * 4096 / (2 * np.pi)
    np.testing.assert_allclose(got[turns > 40], plain[turns > 40], rtol=1e-6)
    np.testing.assert_allclose(got[turns < 0.8], plain[turns < 0.8] / 40,
                               rtol=1e-6)
    between = (turns > 1.5) & (turns < 25)
    assert between.any() and ((got < plain) & (got > plain / 40))[between].all()
    assert rope.yarn_mscale(40.0) == pytest.approx(0.1 * np.log(40) + 1)
    assert rope.yarn_mscale(1.0) == 1.0
    # either pair layout is the one rotation, and split halves is apply_rope's
    x = jax.random.normal(jax.random.key(0), (2, 7, 3, 16))
    at = jnp.arange(7)[None] + jnp.array([[0], [50]])
    freqs = rope.rope_frequencies(16, 10000.0)
    np.testing.assert_allclose(rope.rotate(x, at, freqs),
                               rope.apply_rope(x, at, 10000.0), atol=1e-6)
    pairs = x.reshape(2, 7, 3, 8, 2)
    halves = jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)
    turned = rope.rotate(halves, at, freqs)
    np.testing.assert_allclose(
        rope.rotate(x, at, freqs, interleaved=True),
        jnp.stack(jnp.split(turned, 2, axis=-1), axis=-1).reshape(x.shape),
        atol=1e-6)


# --- group-limited routing -------------------------------------------------------------

def _expert_weights(E=32, H=32, I=24, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    n = jax.random.normal
    return {"router": n(ks[0], (H, E)), "bias": 0.05 * n(ks[1], (E,)),
            "e_gate": n(ks[2], (E, H, I)) * H ** -.5,
            "e_up": n(ks[3], (E, H, I)) * H ** -.5,
            "e_down": n(ks[4], (E, I, H)) * I ** -.5,
            "s_gate": n(ks[5], (H, I)) * H ** -.5,
            "s_up": n(ks[6], (H, I)) * H ** -.5,
            "s_down": n(ks[7], (I, H)) * I ** -.5}


def test_group_limited_route_is_the_loop_written_out():
    """32 experts in 8 groups of 4, 4 groups kept, top 6: a group's score is
    the sum of its two best biased scores, only the kept groups' experts
    stand, the weights are the UNbiased scores over their sum, scaled."""
    w = _expert_weights()
    h = jax.random.normal(jax.random.key(3), (100, 32))
    sel, wts = el.route(h, w["router"], w["bias"], 6, scale=2.5, groups=8,
                        groups_kept=4)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        h, w["router"], precision=jax.lax.Precision.HIGHEST)))
    bias = np.asarray(w["bias"])
    free, _ = el.route(h, w["router"], w["bias"], 6, scale=2.5)
    differs = 0
    for t in range(100):
        biased = s[t] + bias
        group = [np.sort(biased[4 * g:4 * g + 4])[-2:].sum() for g in range(8)]
        kept = np.argsort(group)[-4:]
        standing = [e for e in range(32) if e // 4 in kept]
        want = sorted(standing, key=lambda e: -biased[e])[:6]
        assert sorted(np.asarray(sel[t]).tolist()) == sorted(want)
        picked = s[t][np.asarray(sel[t])]
        np.testing.assert_allclose(wts[t], picked / picked.sum() * 2.5,
                                   rtol=1e-5)
        differs += sorted(np.asarray(free[t]).tolist()) != sorted(want)
    assert differs > 10         # the groups do limit the choice


def test_route_without_groups_is_bit_for_bit_what_it_was():
    """The configurations that have no groups (Trinity's cell): ``route`` at
    its defaults against the lines it had before groups came, the same
    bits."""
    w = _expert_weights(E=16)
    h = jax.random.normal(jax.random.key(8), (300, 32))

    def as_it_was(h, router, bias, k, scale):
        logits = jnp.dot(h.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(s + bias, k)
        wts = jnp.take_along_axis(s, sel, axis=-1)
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20)
        return sel, wts * scale

    for fn in (lambda f: f, jax.jit):
        sel, wts = fn(lambda h: el.route(h, w["router"], w["bias"], 4,
                                         scale=2.448))(h)
        sel0, wts0 = fn(lambda h: as_it_was(h, w["router"], w["bias"], 4,
                                            2.448))(h)
        np.testing.assert_array_equal(sel, sel0)
        np.testing.assert_array_equal(np.asarray(wts).view(np.uint32),
                                      np.asarray(wts0).view(np.uint32))


def test_sixteen_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """What an expert-parallel combine adds up under group-limited routing:
    the routed parts of sixteen shares of two experts plus the shared expert,
    once, equal the layer that holds all 32, which equals the sum written out
    expert by expert; the shares' hits are the choices made."""
    w = _expert_weights()
    h = jax.random.normal(jax.random.key(9), (50, 32))
    kw = dict(experts_per_token=6, route_scale=2.5, groups=8, groups_kept=4,
              counted=jnp.ones(50, bool))
    whole, counts = el.expert_layer_counts(h, w, experts_held=(0, 32), **kw)
    hits = counts[:len(el.TALLY)][0]
    shared = el.swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    parts, counted = shared, 0
    for share in range(16):
        held = {**w, **{k: w[k][2 * share:2 * share + 2]
                        for k in ("e_gate", "e_up", "e_down")}}
        y, n = el.expert_layer_counts(h, held, experts_held=(2 * share, 2),
                                      **kw)
        parts = parts + (y - shared)
        counted += int(n[0])
    assert jnp.abs(parts - whole).max() < 1e-5
    assert counted == int(hits) == 50 * 6
    sel, wts = el.route(h, w["router"], w["bias"], 6, scale=2.5, groups=8,
                        groups_kept=4)
    want = shared + sum(
        jnp.sum(jnp.where(sel == e, wts, 0.0), -1)[:, None]
        * el.swiglu(h, w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        for e in range(32))
    assert jnp.abs(whole - want).max() < 1e-5


@pytest.mark.parametrize("seed", [0, 1, 2147483000])
def test_every_seed_offers_the_held_experts_the_same_load(seed, tiny,
                                                          reference):
    """The selection bias of the program's draw is FITTED to the layer's
    router (``_bias``): on normed tokens of isotropic direction the four held
    experts of the tiny preset (4 of 16, top 4 of 2 groups in 4: 1.0 hit a
    token at even load) take the same load whatever the seed; and it is the
    bias the reference fits, by its own code."""
    cfg, _ = tiny
    key = jax.random.key(seed)
    spec = slm._layer_leaves(cfg, False)
    router, gain, bias = (slm._draw(key, cfg, name, *spec[name], 1)
                          for name in ("router", "norm2", "bias"))
    want = reference.selection_bias(
        reference._key(key, "bias", 1), router, gain.astype(jnp.float32),
        reference.dims(reference_config(cfg)))
    np.testing.assert_allclose(bias, want, atol=2e-5)
    h = jax.random.normal(jax.random.fold_in(key, 9), (1 << 15, 64))
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)) * gain
    first, count = cfg.experts_held

    def held_load(b):
        sel, _ = el.route(h, router, b, cfg.experts_per_token,
                          groups=cfg.n_group, groups_kept=cfg.topk_group)
        return float(jnp.mean(jnp.sum(
            (sel >= first) & (sel < first + count), axis=-1)))

    assert held_load(bias) == pytest.approx(1.0, abs=0.02)
    assert float(jnp.abs(bias).max()) > 1e-3


# --- the family behind the normal path ----------------------------------------------

def test_a_prefix_id_is_a_counted_miss_and_nothing_is_stored(tiny):
    cfg, params = tiny
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=ROWS,
                        prefill_buckets=(16, 128))
    prompt = np.arange(1, 12)
    for _ in range(2):
        req = eng.submit(prompt, SamplingParams(max_new_tokens=2),
                         prefix_id="session-1")
        while not req.done.is_set():
            eng.step()
    assert (eng.prefix_hits, eng.prefix_misses) == (0, 2)
    assert not eng._prefix_cache and eng._prefix_cache_size == 0
    with pytest.raises(ValueError, match="KV handoff"):
        eng.submit(prompt, export=True)


@pytest.mark.parametrize("kwargs,what", [
    ({"dtype": "int8"}, "--dtype int8"),
    ({"kv_cache_int8": True}, "--kv-cache-int8"),
    ({"kv_page_tokens": 16}, "--kv-page-tokens"),
    ({"chips": 2}, "--chips > 1"),
    ({"checkpoint": "/nonexistent"}, "--checkpoint"),
])
def test_what_the_family_lacks_ends_the_boot(kwargs, what):
    from kukeon_tpu.runtime.serving_cell import ServingCell

    args = {"num_slots": 2, "max_seq_len": 64, "checkpoint": None,
            "dtype": None, "chips": 1, **kwargs}
    with pytest.raises(SystemExit, match=what):
        ServingCell("sparse-latent-moe-tiny", **args)


def test_the_cell_boots_and_answers_at_the_tiny_preset():
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("sparse-latent-moe-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None, chips=1)
    assert cell.engine.family is families.of(cell.cfg)
    assert cell.engine.family.name == "sparse_latent_moe"
    out = cell.generate({"prompt": "hello there", "maxNewTokens": 12})
    assert out["numTokens"] == 12


@pytest.mark.parametrize("n, want", [(1, 64), (4096, 4096), (4097, 8192),
                                     (8192, 8192), (8193, 16384),
                                     (20000, 32768), (32764, 32768)])
def test_a_prompt_past_the_largest_bucket_takes_the_next_doubling(n, want):
    from kukeon_tpu.serving.engine import bucket_length

    assert bucket_length(n) == want
