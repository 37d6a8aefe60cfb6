"""Compile-only rehearsal, run by hand in the CPU sandbox: every program shape
a cell's traffic file declares, at the configuration's real sizes, compiled by
the TPU compiler for a DESCRIBED v5e (one device, or a four-device tensor mesh
for a four-chip configuration). Prints the bytes each program keeps resident
on one device. Nothing runs: these are "compile-only" numbers, never times.

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py <config.json> <traffic.json> [...]
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import plugins  # noqa: E402


class AbstractOnly:
    """A checkpoint stream that only knows its shapes (as
    tests/test_chip_compile.py builds its engine)."""

    def __init__(self, tree):
        self.abstract_params = tree

    def __iter__(self):
        return iter(())

    def stat_snapshot(self):
        return {}


def resident(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)


def abstract_engine(config: dict, mesh, pkg_dir: str = plugins.HERE):
    """The engine of a configuration over shapes alone: the family's launcher
    (``launchers/<family>.py``) supplies the program's config, the abstract
    parameter tree and what else its family needs; the levers are the
    configuration file's, as in ``cell_main.CellHost.boot``."""
    from kukeon_tpu.serving import ServingEngine

    s = config["serving"]
    family = plugins.load("launchers", config["reference"],
                          pkg_dir).abstract(config)
    cfg, tree = family.pop("cfg"), family.pop("params")
    eng = ServingEngine(cfg, AbstractOnly(tree), mesh, **family,
                        num_slots=s["num_slots"], max_seq_len=s["max_seq_len"],
                        async_load=True, kv_page_tokens=s["kv_page_tokens"],
                        kv_cache_int8=s["kv_cache_int8"],
                        decode_chunk=s["decode_chunk"])
    return cfg, eng


def rehearse(config: dict, traffic: dict,
             pkg_dir: str = plugins.HERE) -> list[tuple[str, float, float]]:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from kukeon_tpu.parallel import make_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    s = config["serving"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(tensor=s["chips"], devices=topo.devices[:s["chips"]])
    cfg, eng = abstract_engine(config, mesh, pkg_dir)
    repl = NamedSharding(mesh, PartitionSpec())
    kv_sh = eng._cache_shardings()[0]
    B = s["num_slots"]

    def sds(shape, dtype, sh=repl):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    k0 = jax.eval_shape(lambda: jax.random.key(0))
    key = sds(k0.shape, k0.dtype)
    f32, i32 = sds((), jnp.float32), sds((), jnp.int32)

    def kv(rows):
        return sds((cfg.num_layers, 1, rows, cfg.num_kv_heads, cfg.head_dim),
                   cfg.dtype, kv_sh)

    warm = traffic["warmup"]
    jobs = []
    for b in warm["prefill"]:
        jobs.append((f"prefill[{b}]", lambda b=b: eng._prefill.lower(
            eng._abstract_params, sds((1, b), jnp.int32), i32, key,
            f32, i32, f32)))
        jobs.append((f"insert[{b}]", lambda b=b: eng._insert.lower(
            eng._abstract_state(), kv(b), kv(b), i32, i32, i32)))
    for c, t in warm.get("prefill_ext", []):
        jobs.append((f"prefill_ext[{c}+{t}]", lambda c=c, t=t:
                     eng._prefill_ext.lower(
                         eng._abstract_params, kv(c), kv(c), i32,
                         sds((1, t), jnp.int32), i32, key, f32, i32, f32)))
    for k in warm["decode_chunk"]:
        jobs.append((f"decode_chunk[k={k}]", lambda k=k: eng._decode_chunk.lower(
            eng._abstract_params, eng._abstract_state(), key,
            sds((B,), jnp.float32), sds((B,), jnp.int32),
            sds((B,), jnp.float32), k)))
    out = []
    with jax.set_mesh(mesh):
        for name, lower in jobs:
            t0 = time.monotonic()
            compiled = lower().compile()
            out.append((name, resident(compiled) / 1e9,
                        time.monotonic() - t0))
            print(f"{config['name']} x {s['chips']} chip(s): {name}: "
                  f"{out[-1][1]:.2f} GB resident per device, compiled in "
                  f"{out[-1][2]:.0f} s", flush=True)
    return out


if __name__ == "__main__":
    args = sys.argv[1:]
    for cfg_path, traffic_path in zip(args[::2], args[1::2]):
        with open(cfg_path) as f, open(traffic_path) as g:
            rehearse(json.load(f), json.load(g), os.path.dirname(
                os.path.dirname(os.path.abspath(cfg_path))))
