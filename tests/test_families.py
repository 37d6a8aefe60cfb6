"""models/families.py: one record a family, and the dense family's programs
unchanged by the seam that lets a third family in."""

from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from kukeon_tpu.models import llama, moe
from kukeon_tpu.parallel import make_mesh, moe_specs_for_params
from kukeon_tpu.serving import ServingEngine

# sha256 of each program's lowered text at the parent of PR 30 (commit
# f58059e), taken by running THIS function against that tree. JAX's
# compile-cache key is made of this text (locations stripped), so equal text
# means the driver's machine finds the parent's executables again: set-up does
# not move and no cell of the dense family can slow. A PR that changes a dense
# program on purpose takes new hashes from its own tree: PR 33 took the three
# decode chunks' (a step tells its attention which slots are active, and the
# layer scan reads the held stack at the layer's index instead of scanning
# over it); PR 37 took the prefills' and again the decode chunks' (`_qkv`
# holds its three products behind an optimization barrier, which is in the
# text); the inserts and the Mixtral block's are still the text of f58059e.
PARENT = {
    "dense.prefill":
        "e50a0595ffcd63b67d986f0bb5713d04ef92f93b53775dfbd84936b10f7494f9",
    "dense.prefill_ext":
        "c64d4bba5d2f9cfecbdd3e7b8370a2043ed6b2b533b2c2f2f6b6253951fe4010",
    "dense.insert":
        "10856345330b648a26a4eed787f437a3c7c3a254284a22f36fe32a049a49b761",
    "dense.decode_chunk":
        "6e97b20499e55c6d1f19a22514d5ae03c9f4909423d061af765d6a1f2ac1c544",
    "dense.insert_paged":
        "33a5e37d00fe4fe26ca25dddf825aff5b211225d39a4e051d65a4cc54faf8bde",
    "dense.decode_chunk_paged":
        "73c737cfaba0d9cf31216c32beab36761b961c3762a4146b95b102bd7e19fd67",
    "moe.prefill":
        "90cc96c2f17fd443b1a5717fa0ecb43eef2d6214dbb9e62fadcff82c254aeb32",
    "moe.decode_chunk":
        "88c4e173027326e1ecebf12a8a08734809b72b921968ecd918c1e0d7f766a11c",
}


def _lowered(name: str, eng: ServingEngine) -> dict:
    cfg, B = eng.cfg, eng.num_slots
    eng._ensure_loaded()
    key = jax.random.key(1)
    f32, i32 = np.float32, np.int32
    kv = np.zeros((cfg.num_layers, 1, 64, cfg.num_kv_heads, cfg.head_dim),
                  np.dtype(cfg.dtype))
    sample = (key, np.zeros(B, f32), np.zeros(B, i32), np.ones(B, f32))
    tokens = np.zeros((1, 64), i32)
    with jax.set_mesh(eng.mesh):
        if eng.paged:
            ids = np.zeros((64 // eng.page_tokens,), i32)
            bt = np.zeros((B, eng.max_pages_per_slot), i32)
            return {
                name + ".insert_paged": eng._insert_paged.lower(
                    eng.state, kv, kv, 5, ids, 0, i32(1)),
                name + ".decode_chunk_paged": eng._decode_chunk_paged.lower(
                    eng.params, eng.state, bt, *sample, 4)}
        return {
            name + ".prefill": eng._prefill.lower(
                eng.params, tokens, 5, key, f32(0), i32(0), f32(1)),
            name + ".prefill_ext": eng._prefill_ext.lower(
                eng.params, kv, kv, 5, tokens, 3, key, f32(0), i32(0), f32(1)),
            name + ".insert": eng._insert.lower(
                eng.state, kv, kv, 5, 0, i32(1)),
            name + ".decode_chunk": eng._decode_chunk.lower(
                eng.params, eng.state, *sample, 4)}


def lowered_texts() -> dict[str, str]:
    """The engine's jitted programs for the two dense-cache families, lowered
    at a tiny size: the dense family as the benchmark's cells boot it
    (checkpoint-less weights-only int8, contiguous bf16 KV), its paged int8-KV
    variants, and the Mixtral block."""
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    shape = dict(num_slots=2, max_seq_len=128, decode_chunk=4)
    cfg = llama.llama_tiny()
    params = llama.init_quantized_params(jax.random.key(0), cfg)
    mcfg = moe.moe_tiny()
    mparams = moe.init_params(jax.random.key(0), mcfg)
    out = {
        **_lowered("dense", ServingEngine(cfg, params, mesh, **shape)),
        **_lowered("dense", ServingEngine(
            cfg, params, mesh, kv_page_tokens=16, kv_cache_int8=True,
            **shape)),
        **_lowered("moe", ServingEngine(
            mcfg, mparams, mesh, forward_fn=moe.forward,
            param_specs=moe_specs_for_params(mparams), **shape))}
    return {k: out[k].as_text() for k in PARENT}


def test_the_dense_families_programs_lower_to_the_parents_text():
    got = {k: hashlib.sha256(v.encode()).hexdigest()
           for k, v in lowered_texts().items()}
    assert got == PARENT


def test_a_family_is_its_configs_type_and_one_record():
    from kukeon_tpu.models import families, window_moe

    dense = families.of(llama.llama_tiny())
    assert dense.name == "dense_gqa" and dense.layered is None
    assert dense is families.of(llama.llama3_8b())
    assert families.of(moe.moe_tiny()).name == "moe_softmax_topk"
    layered = families.of(window_moe.window_moe_tiny())
    assert layered.layered.counters(window_moe.window_moe_tiny()) \
        == window_moe.COUNTERS
    assert families.find(object()) is None
    with pytest.raises(SystemExit, match="no model family"):
        families.of(object())


def test_two_configurations_of_one_type_are_one_family_with_their_own_counters():
    """Every layer alike, or two kinds of layer with an attention each: the
    type of the config is the family, and what its forwards sum turns on the
    config (``Layered.counters`` as a function of it)."""
    from kukeon_tpu.models import families
    from kukeon_tpu.models import sparse_latent_moe as slm

    alike, mixed = slm.sparse_latent_moe_tiny(), slm.mixed_latent_moe_tiny()
    family = families.of(alike)
    assert family is families.of(mixed) and family.name == "sparse_latent_moe"
    assert family.layered.counters(alike) == slm.COUNTERS
    assert family.layered.counters(mixed) == (slm.COUNTERS
                                              + slm.WINDOW_COUNTERS)
    assert [k.name for k in family.layered.kinds(mixed, 64)] == [
        "latent", "window_latent"]
    assert [k.name for k in family.layered.kinds(alike, 64)] == ["latent"]


def test_the_engine_finds_the_family_of_a_config_by_itself():
    """Callers that build the engine from a config alone (the benchmark's
    compile rehearsal, the fixtures of a second family) pass no family."""
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    mcfg = moe.moe_tiny()
    eng = ServingEngine(mcfg, moe.init_params(jax.random.key(0), mcfg), mesh,
                        num_slots=2, max_seq_len=64)
    assert eng.family.name == "moe_softmax_topk"
    assert eng._forward is moe.forward
    assert [k.name for k in eng._kinds] == ["full"]
    assert eng.generate([1, 2, 3]) is not None
