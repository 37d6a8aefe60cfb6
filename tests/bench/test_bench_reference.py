"""reference/dense_gqa.py against the program's own forward at llama_tiny
widths in float32, and the weight definition against the program's draw."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import llama

CFG = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           vocab_size=512, rope_theta=10000.0, rms_norm_eps=1e-5)
ref = plugins.load("reference", "dense_gqa")
draw = jax.jit(ref.draw_int8, static_argnums=(1, 2, 3))


def _same_int8(q, want):
    """Equal, but for a rounding tie that two compilations of w / s may break
    differently: at most one value in 10^5, never by more than one step."""
    d = np.abs(np.asarray(q, np.int32) - np.asarray(want, np.int32))
    return d.max() <= 1 and (d > 0).mean() <= 1e-5


def _program_cfg():
    return llama.LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, rope_theta=10000.0,
        max_seq_len=256, dtype=jnp.float32, tie_embeddings=False)


@pytest.mark.parametrize("seed", [0, 5, 2147483000])
def test_the_program_draws_the_weights_the_benchmark_defines(seed):
    params = llama.init_quantized_params(jax.random.key(seed), _program_cfg())
    keys = jax.random.split(jax.random.key(seed), len(ref.MATRICES))
    q, s = draw(keys[0], (512, 128), 128, 1)
    assert _same_int8(q, params["embed"]["q"])
    np.testing.assert_allclose(s[:, 0], params["embed"]["s"], rtol=1e-6)
    for m, (name, shape, fan_in) in enumerate([
            ("wq", (128, 128), 128), ("wk", (128, 64), 128),
            ("wv", (128, 64), 128), ("wo", (128, 128), 128),
            ("w_gate", (128, 256), 128), ("w_up", (128, 256), 128),
            ("w_down", (256, 128), 256)], start=1):
        for layer in range(2):
            q, s = draw(jax.random.split(keys[m], 2)[layer], shape, fan_in, 0)
            assert _same_int8(q, params["layers"][name]["q"][layer]), name
            np.testing.assert_allclose(
                s[0], params["layers"][name]["s"][layer], rtol=1e-6)
    q, _s = draw(keys[8], (128, 512), 128, 0)
    assert _same_int8(q, params["lm_head"]["q"])


@pytest.mark.parametrize("seed", [1, 7])
def test_reference_agrees_with_the_programs_forward_in_float32(seed):
    cfg = _program_cfg()
    params = llama.init_quantized_params(jax.random.key(seed), cfg)
    toks = np.random.default_rng(seed).integers(0, 512, 200).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = llama.forward(params, cfg, jnp.asarray(toks)[None],
                                  jnp.arange(200)[None])
    got = ref.logits_at(CFG, seed, [toks, toks[:150]],
                        [np.arange(100, 200), np.arange(20, 50)], 256)
    # float32 accumulation order is the only difference: logits are O(1)
    np.testing.assert_allclose(got[0], np.asarray(logits[0, 100:200]),
                               atol=5e-5)
    np.testing.assert_allclose(got[1], np.asarray(logits[0, 20:50]),
                               atol=5e-5)


@pytest.mark.parametrize("precision,least", [("a8", 0.01), ("w4", 0.5)])
def test_lower_precision_moves_the_logits(precision, least):
    toks = np.random.default_rng(3).integers(0, 512, 200).astype(np.int32)
    at = [np.arange(100, 200)]
    full = ref.logits_at(CFG, 3, [toks], at, 256)[0]
    low = ref.logits_at(CFG, 3, [toks], at, 256,
                        precision=precision)[0]
    gap = full.max(-1) - full[np.arange(100), low.argmax(-1)]
    assert gap.max() > least
