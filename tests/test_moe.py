"""Mixtral-style MoE model: routing numerics, expert parallelism, cache
decode, and the expert-sharded training step (the ``expert`` mesh axis's
workload — dispatch/combine all-to-alls inserted by GSPMD)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama, moe
from kukeon_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def tiny():
    cfg = moe.moe_tiny()
    params = moe.init_params(jax.random.key(0), cfg)
    return cfg, params


def _naive_moe_block(h, w, cfg):
    """Reference: per-token python loop over top-k experts (no capacity)."""
    B, S, H = h.shape
    x = h.reshape(-1, H)
    logits = np.asarray(x.astype(jnp.float32) @ w["router"])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    out = np.zeros_like(np.asarray(x), dtype=np.float32)
    K = cfg.experts_per_token
    for n in range(x.shape[0]):
        top = np.argsort(-probs[n])[:K]
        gates = probs[n][top]
        gates = gates / gates.sum()
        for gate, e in zip(gates, top):
            xe = np.asarray(x[n]).astype(np.float32)
            g = np.asarray(jax.nn.silu(jnp.asarray(xe @ np.asarray(w["w_gate"][e], np.float32))))
            u = xe @ np.asarray(w["w_up"][e], np.float32)
            y = (g * u) @ np.asarray(w["w_down"][e], np.float32)
            out[n] += gate * y
    return out.reshape(B, S, H)


def test_moe_block_matches_naive_loop(tiny):
    """Dense-dispatch einsum formulation == per-token expert loop when
    capacity is large enough that nothing drops."""
    cfg, params = tiny
    w = {k: v[0] for k, v in params["layers"].items()}   # layer 0 slice
    h = jax.random.normal(jax.random.key(3), (2, 6, cfg.hidden_size), jnp.float32)

    got, aux = moe.moe_block(h, w, cfg)
    want = _naive_moe_block(h, w, cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    assert float(aux["load_balance"]) > 0.0
    assert float(aux["router_z"]) >= 0.0


def test_capacity_drops_overflow_tokens(tiny):
    """With capacity 1 slot per expert, most tokens overflow: the MoE output
    must stay finite and bounded (dropped tokens contribute zero, residual
    carries them)."""
    cfg, params = tiny
    cfg1 = dataclasses.replace(cfg, capacity_factor=1e-6)   # floor -> K slots
    w = {k: v[0] for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.key(4), (2, 8, cfg.hidden_size), jnp.float32)
    got, _ = moe.moe_block(h, w, cfg1)
    assert np.isfinite(np.asarray(got)).all()
    # Strictly fewer tokens served than the no-drop run touches.
    full, _ = moe.moe_block(h, w, cfg)
    served = np.count_nonzero(np.abs(np.asarray(got)).sum(-1) > 1e-9)
    served_full = np.count_nonzero(np.abs(np.asarray(full)).sum(-1) > 1e-9)
    assert served < served_full


def test_forward_shapes_and_determinism(tiny):
    cfg, params = tiny
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    logits, cache = moe.forward(params, cfg, tokens, positions)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert cache is None
    logits2, _ = moe.forward(params, cfg, tokens, positions)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))


def test_cached_decode_matches_full_forward(tiny):
    """Prefill-into-cache + single-token decode == uncached full forward at
    the same positions (the llama.KVCache layout carried over)."""
    from kukeon_tpu.models.llama import KVCache

    cfg, params = tiny
    B, S = 1, 12
    tokens = jax.random.randint(jax.random.key(2), (B, S + 1), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S + 1, dtype=jnp.int32)[None, :], (B, S + 1))

    full_logits, _ = moe.forward(params, cfg, tokens, positions)

    cache = KVCache.create(cfg, B, 32)
    _, cache = moe.forward(params, cfg, tokens[:, :S], positions[:, :S], cache)
    step_logits, cache = moe.forward(
        params, cfg, tokens[:, S:S + 1], positions[:, S:S + 1], cache
    )
    np.testing.assert_allclose(
        np.asarray(step_logits[0, 0]), np.asarray(full_logits[0, S]),
        rtol=2e-4, atol=2e-4,
    )


def test_expert_parallel_mesh_parity(tiny):
    """expert=2 x tensor=2 sharded forward == single-device forward: the
    all-to-all dispatch must not change numerics."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kukeon_tpu.parallel import moe_specs_for_params

    cfg, params = tiny
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    want, _ = moe.forward(params, cfg, tokens, positions)

    mesh = make_mesh(expert=2, tensor=2, data=2)
    specs = moe_specs_for_params(params)
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P),
    )
    with jax.set_mesh(mesh):
        got, _ = jax.jit(
            lambda p, t, pos: moe.forward(p, cfg, t, pos)
        )(sharded, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_load_balance_loss_semantics(tiny):
    """Switch LB loss == 1.0 under perfectly uniform routing; >> 1 when the
    router collapses onto one expert."""
    cfg, _ = tiny
    E = cfg.num_experts
    N, H = 64, cfg.hidden_size
    h = jax.random.normal(jax.random.key(6), (1, N, H), jnp.float32)
    w_shapes = moe.init_params(jax.random.key(7), cfg)["layers"]
    w = {k: v[0] for k, v in w_shapes.items()}

    # Uniform router: zero logits -> equal probs; first-choice assignment is
    # argmax tie-broken to expert 0, so use tiny symmetric noise instead.
    w_uni = dict(w)
    w_uni["router"] = jnp.zeros((H, E), jnp.float32)
    _, aux_uni = moe.moe_block(h, w_uni, cfg)
    # f_e ~ onehot ties all to expert 0 with zero logits; accept [1, E].
    assert 1.0 <= float(aux_uni["load_balance"]) <= E + 1e-3

    # Collapsed router: huge bias onto expert 0 -> f_0 = P_0 = 1 -> loss = E.
    w_col = dict(w)
    router = np.zeros((H, E), np.float32)
    h_col = jnp.ones((1, N, H), jnp.float32)
    router[:, 0] = 1.0
    w_col["router"] = jnp.asarray(router)
    _, aux_col = moe.moe_block(h_col, w_col, cfg)
    assert float(aux_col["load_balance"]) >= E - 1e-2


def test_moe_train_step_on_expert_mesh():
    """One full MoE training step over an expert x tensor x data mesh:
    finite loss, step increments, metrics include the aux terms."""
    from kukeon_tpu.training import create_moe_train_state, make_moe_train_step
    from kukeon_tpu.training.train_step import make_optimizer

    cfg = moe.moe_tiny()
    mesh = make_mesh(expert=2, tensor=2, data=2)
    with jax.set_mesh(mesh):
        optimizer = make_optimizer(warmup_steps=1, total_steps=10)
        state, optimizer = create_moe_train_state(cfg, mesh, jax.random.key(0), optimizer)
        train_step, batch_sharding = make_moe_train_step(cfg, mesh, optimizer)

        B, S = 4, 32
        tokens = jax.device_put(
            jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size),
            batch_sharding,
        )
        targets = jnp.roll(tokens, -1, axis=1)
        mask = jax.device_put(jnp.ones((B, S), jnp.float32), batch_sharding)
        state, metrics = train_step(state, tokens, targets, mask)
        loss0 = float(metrics["loss"])
        state, metrics = train_step(state, tokens, targets, mask)
    assert np.isfinite(loss0)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 2
    assert float(metrics["load_balance"]) > 0
    assert "ce" in metrics and "router_z" in metrics


def test_moe_serves_through_engine(tiny):
    """The continuous-batching engine is model-pluggable: moe.forward +
    expert specs serve through it, and greedy outputs match a direct
    uncached forward argmax loop."""
    from kukeon_tpu.parallel import moe_specs_for_params
    from kukeon_tpu.serving import SamplingParams, ServingEngine

    cfg, params = tiny
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=64,
                        forward_fn=moe.forward,
                        param_specs=moe_specs_for_params(params))
    prompt = np.arange(2, 12, dtype=np.int32) % cfg.vocab_size
    got = eng.generate(prompt, SamplingParams(temperature=0.0, max_new_tokens=6))

    tokens = list(prompt)
    want = []
    for _ in range(6):
        t = jnp.asarray(tokens, jnp.int32)[None, :]
        pos = jnp.arange(len(tokens), dtype=jnp.int32)[None, :]
        logits, _ = moe.forward(params, cfg, t, pos)
        nxt = int(jnp.argmax(logits[0, -1]))
        want.append(nxt)
        tokens.append(nxt)
    assert got == want


def test_moe_serving_cell_http_roundtrip():
    """ServingCell boots a mixtral-tiny engine and answers /v1/generate
    (model registry + engine pluggability end to end, no daemon)."""
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("mixtral-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None)
    out = cell.generate({"prompt": "hi", "maxNewTokens": 4})
    assert out["numTokens"] == 4
    assert len(out["tokens"]) == 4

    with pytest.raises(SystemExit, match="kv-cache-int8"):
        ServingCell("mixtral-tiny", num_slots=2, max_seq_len=64,
                    checkpoint=None, dtype=None, kv_cache_int8=True)


def test_hf_mixtral_checkpoint_roundtrip(tmp_path, tiny):
    """moe params written in the HF Mixtral safetensors layout load back
    identically through hf_convert.load_moe_params (incl. the transposes),
    and the loaded tree's forward matches the original's."""
    import json

    from safetensors.numpy import save_file

    from kukeon_tpu.models import hf_convert

    cfg, params = tiny
    L, E = cfg.num_layers, cfg.num_experts
    flat = {
        "model.embed_tokens.weight": np.asarray(params["embed"], np.float32),
        "model.norm.weight": np.asarray(params["final_norm"], np.float32),
    }
    lw = params["layers"]
    for i in range(L):
        p = f"model.layers.{i}."
        flat[p + "input_layernorm.weight"] = np.asarray(lw["attn_norm"][i], np.float32)
        flat[p + "post_attention_layernorm.weight"] = np.asarray(lw["mlp_norm"][i], np.float32)
        for ours, hf in (("wq", "q_proj"), ("wk", "k_proj"),
                         ("wv", "v_proj"), ("wo", "o_proj")):
            flat[p + f"self_attn.{hf}.weight"] = np.ascontiguousarray(
                np.asarray(lw[ours][i], np.float32).T)
        flat[p + "block_sparse_moe.gate.weight"] = np.ascontiguousarray(
            np.asarray(lw["router"][i], np.float32).T)
        for e in range(E):
            q = f"{p}block_sparse_moe.experts.{e}."
            flat[q + "w1.weight"] = np.ascontiguousarray(
                np.asarray(lw["w_gate"][i, e], np.float32).T)
            flat[q + "w3.weight"] = np.ascontiguousarray(
                np.asarray(lw["w_up"][i, e], np.float32).T)
            flat[q + "w2.weight"] = np.ascontiguousarray(
                np.asarray(lw["w_down"][i, e], np.float32).T)
    save_file(flat, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "architectures": ["MixtralForCausalLM"],
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": L, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_local_experts": E, "num_experts_per_tok": cfg.experts_per_token,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_seq_len,
        "tie_word_embeddings": True,
    }))

    loaded, lcfg = hf_convert.load_moe_params(str(tmp_path), dtype=jnp.float32)
    assert lcfg.num_experts == E and lcfg.experts_per_token == cfg.experts_per_token
    # capacity_factor is a serving knob, not an HF field; align for parity.
    lcfg = dataclasses.replace(lcfg, capacity_factor=cfg.capacity_factor)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0, rtol=0)

    tokens = jax.random.randint(jax.random.key(8), (1, 8), 0, cfg.vocab_size)
    positions = jnp.arange(8, dtype=jnp.int32)[None, :]
    want, _ = moe.forward(params, cfg, tokens, positions)
    got, _ = moe.forward(loaded, lcfg, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_inference_capacity_never_drops_decode_tokens(tiny):
    """Serving (cache-marked) capacity is exact for decode-sized batches:
    under routing collapse the training drop policy zeroes overflow tokens'
    expert compute, the inference policy must not (code-review r5)."""
    cfg, params = tiny
    tight = dataclasses.replace(cfg, capacity_factor=0.5)
    w = {k: v[0] for k, v in params["layers"].items()}
    # Collapse the router onto expert 0 for every token.
    w = dict(w)
    router = np.zeros((cfg.hidden_size, cfg.num_experts), np.float32)
    router[:, 0] = 1.0
    w["router"] = jnp.asarray(router)
    h = jnp.ones((2, 8, cfg.hidden_size), jnp.float32)   # N=16 tokens

    want = _naive_moe_block(h, w, tight)                 # no-drop reference
    got_inf, _ = moe.moe_block(h, w, tight, inference=True)
    np.testing.assert_allclose(np.asarray(got_inf), want, rtol=2e-4, atol=2e-4)

    got_train, _ = moe.moe_block(h, w, tight)            # drops by design
    assert not np.allclose(np.asarray(got_train), want, rtol=2e-4, atol=2e-4)


def test_quantized_moe_forward_tracks_fp(tiny):
    """Weights-only int8 MoE: logits stay close to full-precision (per-
    channel symmetric quantization noise only), and the quantized tree
    serves through the engine on an expert-sharded mesh identically to a
    single device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kukeon_tpu.parallel import moe_specs_for_params
    from kukeon_tpu.serving import SamplingParams, ServingEngine

    cfg, params = tiny
    qp = moe.quantize_params(params)
    B, S = 1, 12
    tokens = jax.random.randint(jax.random.key(11), (B, S), 0, cfg.vocab_size)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    fp, _ = moe.forward(params, cfg, tokens, positions)
    q, _ = moe.forward(qp, cfg, tokens, positions)
    err = np.abs(np.asarray(q) - np.asarray(fp)).mean()
    scale = np.abs(np.asarray(fp)).mean() + 1e-9
    assert err / scale < 0.05, f"relative error {err/scale:.3f}"

    specs = moe_specs_for_params(qp)
    mesh2 = make_mesh(expert=2, tensor=2, data=2)
    eng2 = ServingEngine(cfg, qp, mesh2, num_slots=2, max_seq_len=64,
                         forward_fn=moe.forward, param_specs=specs)
    mesh1 = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng1 = ServingEngine(cfg, qp, mesh1, num_slots=2, max_seq_len=64,
                         forward_fn=moe.forward, param_specs=specs)
    prompt = np.arange(2, 12, dtype=np.int32) % cfg.vocab_size
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    assert eng2.generate(prompt, sp) == eng1.generate(prompt, sp)


def test_quantized_moe_serving_cell():
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("mixtral-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype="int8")
    out = cell.generate({"prompt": "hi", "maxNewTokens": 3})
    assert out["numTokens"] == 3


def test_a_quantized_decode_step_is_the_step_over_the_dequantized_weights(tiny):
    """The decode forward over int8 leaves (attention trunk through
    ``llama.mm``, expert stacks through ``_expert_mm``: the dequant fused into
    each dot) gives the logits of the same step over ``q * s`` held as plain
    matrices."""
    cfg, params = tiny
    qp = moe.quantize_params(params)
    axis = {"embed": 1, "lm_head": 0, "wq": 1, "wk": 1, "wv": 1, "wo": 1,
            "w_gate": 2, "w_up": 2, "w_down": 2}

    def plain(tree):
        return {name: (w["q"].astype(jnp.float32)
                       * jnp.expand_dims(w["s"], axis[name])
                       if llama._is_q(w) else w) for name, w in tree.items()}

    fp = {**plain({k: w for k, w in qp.items() if k != "layers"}),
          "layers": plain(qp["layers"])}
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    cache = moe.KVCache.create(cfg, B, 32)
    _, cache = moe.forward(qp, cfg, tokens, positions, cache)

    step = jax.random.randint(jax.random.key(4), (B, 1), 0, cfg.vocab_size)
    step_pos = cache.lengths[:, None]
    got, _ = moe.forward(qp, cfg, step, step_pos, cache)
    want, _ = moe.forward(fp, cfg, step, step_pos, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
