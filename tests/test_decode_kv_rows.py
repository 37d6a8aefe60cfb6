"""``kukeon_engine_decode_kv_rows_total{what}`` (serving/engine.py): what the
decode chunks the engine dispatched had before them (``held``) and what their
attention fetched (``read``), from the host's own slot lengths and the same
question the attention asks of its shapes (``ops.attention.decode_block_rows``).
On the CPU the XLA body reads every row, so read == held; where the kernel
runs, read is whole blocks up to each active slot's last live row, and an
inactive slot reads nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama, window_moe
from kukeon_tpu.obs import render
from kukeon_tpu.ops import attention
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

ROWS, SLOTS = 128, 3


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(tensor=1, devices=jax.devices()[:1])


def _dense(mesh, **kw):
    cfg = llama.llama_tiny()
    return cfg, ServingEngine(cfg, llama.init_params(jax.random.key(0), cfg),
                              mesh, num_slots=SLOTS, max_seq_len=ROWS,
                              decode_chunk=16, **kw)


def _rows(eng) -> dict[str, int]:
    return {lab["what"]: int(v) for lab, v in eng._m_kv_rows.samples()}


def test_on_the_cpu_every_held_row_is_read_and_counted_once_a_chunk(mesh):
    cfg, eng = _dense(mesh)
    assert eng._kv_blocks == (None,)
    req = eng.submit(np.arange(1, 9, dtype=np.int32),
                     SamplingParams(max_new_tokens=12))
    while not req.done.is_set():
        eng.step()
    steps = sum(int(lab["k"]) * int(v) for lab, v in eng._m_chunks.samples())
    assert steps >= 11
    want = steps * cfg.num_layers * SLOTS * ROWS
    assert _rows(eng) == {"held": want, "read": want}
    text = render(eng.registry)
    assert 'kukeon_engine_decode_kv_rows_total{what="read"}' in text


@pytest.mark.parametrize("lengths, read", [
    ([], 0),                        # no active slot: nothing is fetched
    ([1], 16), ([15], 16), ([16], 16), ([17], 32),
    ([128], 128),                   # full to the last row
    ([5, 40, 128], 16 + 48 + 128),
    ([300], 128),                   # never more than the slot holds
])
def test_where_the_kernel_runs_read_is_whole_blocks_of_live_rows(
        mesh, lengths, read):
    cfg, eng = _dense(mesh)
    eng._kv_blocks = (16,)
    assert eng._decode_kv_rows(lengths) == (
        cfg.num_layers * SLOTS * ROWS, cfg.num_layers * read)


def test_a_layered_familys_kinds_are_weighted_by_their_layers(mesh):
    """Rings hold a window's rows and there are several of them: the share
    follows bytes, not one layer of each kind."""
    cfg = window_moe.window_moe_tiny()
    eng = ServingEngine(cfg, window_moe.init_params(jax.random.key(0), cfg),
                        mesh, num_slots=2, max_seq_len=64)
    by_name = {kd.name: kd for kd in eng._kinds}
    ring, full = by_name["window"], by_name["full"]
    assert ring.ring and ring.rows == 8 and full.rows == 64
    assert eng._kv_blocks == (None, None)
    held = 2 * (len(ring.layers) * 8 + len(full.layers) * 64)
    assert eng._decode_kv_rows([5, 40]) == (held, held)
    eng._kv_blocks = (8, 8)
    # 5 tokens: one block of each kind; 40: the whole ring, five full blocks
    assert eng._decode_kv_rows([5, 40]) == (
        held, len(ring.layers) * (8 + 8) + len(full.layers) * (8 + 40))


@pytest.mark.parametrize("why, kw", [
    ("the cpu", {}),
    ("an int8 cache", {"cache_dtype": jnp.int8, "backend": "tpu"}),
    ("two devices", {"devices": 2, "backend": "tpu"}),
    ("a cache wider than the activations",
     {"cache_dtype": jnp.float32, "backend": "tpu"}),
    ("rows no block tiles", {"rows": 2000, "backend": "tpu"}),
    ("a head narrower than a lane row", {"head_dim": 64, "backend": "tpu"}),
])
def test_the_xla_body_serves(monkeypatch, why, kw):
    kw = dict(kw)
    monkeypatch.setattr(jax, "default_backend",
                        lambda b=kw.pop("backend", "cpu"): b)
    args = dict(heads=32, kv_heads=8, rows=2048, head_dim=128,
                dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16, devices=1)
    assert attention.decode_block_rows(**{**args, **kw}) is None, why


@pytest.mark.parametrize("heads, rows", [(32, 2048), (48, 8192), (48, 4096)])
def test_the_kernel_serves_a_bf16_cache_on_one_tpu(monkeypatch, heads, rows):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.decode_block_rows(
        heads, 8, rows, 128, jnp.bfloat16, jnp.bfloat16, 1) == 512
