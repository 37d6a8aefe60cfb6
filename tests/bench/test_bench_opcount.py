"""opcount/ against numbers worked by hand from the published sizes."""

import json
import os

import pytest

from benchmark import plugins


def _cfg(name):
    with open(os.path.join(plugins.HERE, "configs", name + ".json")) as f:
        return json.load(f)


MISTRAL = "mistral-7b-v0.3-int8"
CODESTRAL = "codestral-22b-v0.1-int8-tp4"
# per layer: H*(Q+2KV) + Q*H + 3*H*I
HAND = {
    MISTRAL: {"layer": 4096 * (4096 + 2048) + 4096 * 4096 + 3 * 4096 * 14336,
              "head": 4096 * 32768, "L": 32, "kv_row": 2 * 32 * 1024 * 2},
    CODESTRAL: {"layer": 6144 * (6144 + 2048) + 6144 * 6144 + 3 * 6144 * 16384,
                "head": 6144 * 32768, "L": 56, "kv_row": 2 * 56 * 1024 * 2},
}


@pytest.mark.parametrize("name,params_g", [(MISTRAL, 7.25), (CODESTRAL, 22.2)])
def test_parameter_count_matches_the_published_model(name, params_g):
    h = HAND[name]
    total = h["L"] * h["layer"] + 2 * h["head"]      # + embedding
    assert total / 1e9 == pytest.approx(params_g, abs=0.05)


@pytest.mark.parametrize("name", [MISTRAL, CODESTRAL])
def test_decode_step_bytes_and_flops(name):
    cfg, h = _cfg(name), HAND[name]
    chips = cfg["serving"]["chips"]
    dec = plugins.load("opcount", "decode_chunk").count
    need = dec(cfg, slots=8, kv_rows=8000, chips=chips)
    weights = h["L"] * h["layer"] + h["head"]
    kv = h["kv_row"] * 8000
    assert need["bytes"] * chips == pytest.approx(weights + kv, rel=0.002)
    assert need["bytes"] * chips > weights + kv
    attn = 4 * h["L"] * cfg["num_attention_heads"] * 128 * 8000
    assert need["flops"] * chips == pytest.approx(2 * weights * 8 + attn)
    # no live rows, no slots: the weights alone
    idle = dec(cfg, slots=0, kv_rows=0, chips=1)
    assert idle["bytes"] == pytest.approx(weights, rel=0.002)


def test_mistral_kv_is_131_kb_a_token_and_codestral_229():
    assert HAND[MISTRAL]["kv_row"] == 131072
    assert HAND[CODESTRAL]["kv_row"] == 229376


@pytest.mark.parametrize("name", [MISTRAL, CODESTRAL])
def test_prefill_flops(name):
    cfg, h = _cfg(name), HAND[name]
    pre = plugins.load("opcount", "prefill").count
    need = pre(cfg, new_tokens=1000, cached_tokens=0, chips=1)
    dense = 2 * h["L"] * h["layer"] * 1000 + 2 * h["head"]
    attn = 4 * h["L"] * cfg["num_attention_heads"] * 128 * (1000 * 1001 / 2)
    assert need["flops"] == pytest.approx(dense + attn)
    hit = pre(cfg, new_tokens=100, cached_tokens=900, chips=1)
    attn_hit = 4 * h["L"] * cfg["num_attention_heads"] * 128 * (100 * 900 + 100 * 101 / 2)
    assert hit["flops"] == pytest.approx(
        2 * h["L"] * h["layer"] * 100 + 2 * h["head"] + attn_hit)
    assert pre(cfg, 1000, 0, chips=4)["flops"] == pytest.approx(need["flops"] / 4)


def test_roofline_of_one_mistral_decode_step_is_bound_by_bytes():
    cfg = _cfg(MISTRAL)
    with open(os.path.join(plugins.HERE, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    need = plugins.load("opcount", "decode_chunk").count(cfg, 8, 8000)
    t_bytes = need["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = need["flops"] / peak["bf16_flops_per_s"]
    assert t_bytes > 5 * t_flops
    assert 0.009 < t_bytes < 0.012        # 7.1 GB + 1 GB of KV at 819 GB/s
