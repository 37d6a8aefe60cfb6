"""KUKE003/KUKE004/KUKE014/KUKE015 — jit-stability, placement, and
observability of the engine's compiled programs.

The engine's performance story rests on "decode never recompiles": its
jitted programs are built once in ``_build_programs`` and every dispatch
must hit the tracing cache. Two statically-checkable ways to break that:

- **KUKE003 — container literals in traced positions.** A Python
  list/tuple/dict/set literal (or comprehension) passed where the program
  expects an array becomes part of the *pytree structure* of the call, so
  its length/keys are baked into the cache key — a per-request-sized list
  mints a fresh compile per length. Arrays (numpy or device) are the only
  safe payload in a traced position. Positions declared ``static_argnums``
  are exempt (their values are legitimately part of the cache key; the
  engine bounds them separately, e.g. chunk sizes rounded to powers of 4).
- **KUKE004 — closing over mutable engine state.** The program bodies are
  closures; a read of ``self.X`` inside one is evaluated at *trace* time
  and frozen into every cached executable. For init-frozen configuration
  that is fine (and used: ``self.max_seq_len``, ``self._bucket``); for
  mutable scheduler state (``self.state``, ``self._slot_len``, the pool…)
  it is a silent staleness bug — the compiled program keeps the value the
  first trace saw. Only the declared frozen allowlist may appear.

A third statically-checkable property guards the multi-chip story:

- **KUKE014 — implicit placement on a mesh-enabled engine.** The engine
  serves on an explicit mesh (1..N chips); a ``jax.jit`` without
  ``in_shardings``/``out_shardings`` leaves placement to GSPMD inference,
  which can silently replicate a sharded KV pool (N× HBM) or insert a
  resharding transfer on the decode path. Every jitted-program definition
  in ``_build_programs`` must pass BOTH keywords — replication is fine,
  but it must be spelled (``NamedSharding(mesh, PartitionSpec())``), never
  defaulted.

A fourth guards the roofline instrumentation:

- **KUKE015 — programs must register with the program-timer seam.** Every
  jitted program wrapped in ``_build_programs`` must pass a ``timer=``
  keyword to ``CompileTracker.wrap`` (``timer=tm.track("<program>")``).
  A program wrapped without one dispatches invisibly to the per-program
  counters (``kukeon_program_dispatch_total``,
  ``kukeon_program_seconds``) — the flight recorder's step records and
  the benchmark's window counts would silently under-report what ran.

All rules are scoped to ``serving/engine.py``'s ``ServingEngine``: the
pass reads ``_build_programs`` to learn which inner functions are jitted
(and their ``static_argnums``), then checks every call site of the seven
``self._<program>`` attributes across the class (including the
``.lower(...)`` AOT path in ``precompile``).
"""

from __future__ import annotations

import ast
from typing import Sequence

from kukeon_tpu.analysis.core import (
    Finding, SourceFile, is_self_attr, register_pass,
)
from kukeon_tpu.analysis.hostsync import (
    ENGINE_CLASS, ENGINE_FILE_SUFFIX, JITTED_PROGRAMS,
)

# self attributes a jitted program body may read: frozen at __init__ and
# never reassigned while the engine serves (the lint that keeps this list
# honest is KUKE005 — none of these may gain a locked writer).
FROZEN_SELF_ATTRS = frozenset({
    "cfg", "mesh", "max_seq_len", "prefill_buckets", "page_tokens",
    "paged", "num_slots", "kv_cache_int8", "max_pages_per_slot",
    "kv_pool_pages", "eos_ids", "decode_chunk", "_bucket",
    "_fwd_logit_positions", "_forward",
})

CONTAINER_NODES = (ast.List, ast.Tuple, ast.Dict, ast.Set,
                   ast.ListComp, ast.DictComp, ast.SetComp,
                   ast.GeneratorExp)


def _static_argnums(jit_call: ast.Call) -> tuple[int, ...]:
    for kw in jit_call.keywords:
        if kw.arg == "static_argnums":
            if isinstance(kw.value, ast.Tuple):
                return tuple(
                    n.value for n in kw.value.elts
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, int))
            if (isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, int)):
                return (kw.value.value,)
    return ()


def _find_jit_call(node: ast.AST) -> ast.Call | None:
    """The ``jax.jit(fn, ...)`` call inside an expression like
    ``ct.wrap(jax.jit(fn, ...), "name")`` or a bare ``jax.jit(fn)``."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        f = sub.func
        if (isinstance(f, ast.Attribute) and f.attr == "jit"
                and isinstance(f.value, ast.Name) and f.value.id == "jax"):
            return sub
        if isinstance(f, ast.Name) and f.id == "jit":
            return sub
    return None


def _collect_programs(build: ast.FunctionDef) -> tuple[
        dict[str, str], dict[str, tuple[int, ...]]]:
    """(program attr -> inner function name, program attr -> static nums)
    from ``_build_programs``'s ``self._X = ...jax.jit(fn, ...)...``."""
    fn_of: dict[str, str] = {}
    statics: dict[str, tuple[int, ...]] = {}
    for node in ast.walk(build):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not (is_self_attr(target) and target.attr in JITTED_PROGRAMS):
            continue
        jit_call = _find_jit_call(node.value)
        if jit_call is None or not jit_call.args:
            continue
        inner = jit_call.args[0]
        if isinstance(inner, ast.Name):
            fn_of[target.attr] = inner.id
        statics[target.attr] = _static_argnums(jit_call)
    return fn_of, statics


@register_pass(("KUKE003", "KUKE004"))
def check_jit_stability(sources: Sequence[SourceFile],
                        package_root: str) -> list[Finding]:
    findings: list[Finding] = []
    for src in sources:
        if not src.rel.endswith(ENGINE_FILE_SUFFIX):
            continue
        for cls in src.tree.body:
            if not (isinstance(cls, ast.ClassDef)
                    and cls.name == ENGINE_CLASS):
                continue
            build = next(
                (m for m in cls.body if isinstance(m, ast.FunctionDef)
                 and m.name == "_build_programs"), None)
            if build is None:
                continue
            fn_of, statics = _collect_programs(build)

            # KUKE004: traced bodies may only read frozen self attrs. Every
            # function defined directly in _build_programs is traced — the
            # jitted programs plus helpers they call (walking each one also
            # covers its nested scan bodies).
            prog_of_fn = {v: k for k, v in fn_of.items()}
            inner_defs = {
                n.name: n for n in build.body
                if isinstance(n, ast.FunctionDef)}
            for fname, body in inner_defs.items():
                prog = prog_of_fn.get(fname, fname)
                for node in ast.walk(body):
                    if (is_self_attr(node)
                            and isinstance(node.ctx, ast.Load)
                            and node.attr not in FROZEN_SELF_ATTRS):
                        findings.append(Finding(
                            "KUKE004", src.rel, node.lineno,
                            f"jitted program {prog} ({fname}) closes over "
                            f"mutable engine state self.{node.attr}; its "
                            f"value is frozen at trace time — pass it as "
                            f"an argument or add it to the frozen "
                            f"allowlist if it is init-immutable",
                            scope=f"{cls.name}.{fname}",
                            detail=f"self.{node.attr}"))

            # KUKE003: container literals in traced call-site positions.
            for meth in cls.body:
                if (not isinstance(meth, ast.FunctionDef)
                        or meth.name == "_build_programs"):
                    continue
                for node in ast.walk(meth):
                    if not isinstance(node, ast.Call):
                        continue
                    prog = _called_program(node)
                    if prog is None or prog not in fn_of:
                        continue
                    static = set(statics.get(prog, ()))
                    for i, arg in enumerate(node.args):
                        if i in static:
                            continue
                        if isinstance(arg, CONTAINER_NODES):
                            findings.append(Finding(
                                "KUKE003", src.rel, arg.lineno,
                                f"Python container literal passed in "
                                f"traced position {i} of jitted program "
                                f"{prog}: its structure becomes part of "
                                f"the compile cache key (recompile per "
                                f"length) — pass an array",
                                scope=f"{cls.name}.{meth.name}",
                                detail=f"{prog}[{i}]"))
    return findings


@register_pass(("KUKE014",))
def check_jit_shardings(sources: Sequence[SourceFile],
                        package_root: str) -> list[Finding]:
    """Every jitted-program definition must place its data explicitly."""
    findings: list[Finding] = []
    for src in sources:
        if not src.rel.endswith(ENGINE_FILE_SUFFIX):
            continue
        for cls in src.tree.body:
            if not (isinstance(cls, ast.ClassDef)
                    and cls.name == ENGINE_CLASS):
                continue
            build = next(
                (m for m in cls.body if isinstance(m, ast.FunctionDef)
                 and m.name == "_build_programs"), None)
            if build is None:
                continue
            for node in ast.walk(build):
                if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                    continue
                target = node.targets[0]
                if not (is_self_attr(target)
                        and target.attr in JITTED_PROGRAMS):
                    continue
                jit_call = _find_jit_call(node.value)
                if jit_call is None:
                    continue
                present = {kw.arg for kw in jit_call.keywords}
                missing = [k for k in ("in_shardings", "out_shardings")
                           if k not in present]
                if missing:
                    findings.append(Finding(
                        "KUKE014", src.rel, jit_call.lineno,
                        f"jitted program {target.attr} is compiled without "
                        f"explicit {' / '.join(missing)}: on a multi-chip "
                        f"mesh GSPMD would infer placement (silent KV-pool "
                        f"replication or decode-path resharding) — spell "
                        f"the sharding, using NamedSharding(mesh, "
                        f"PartitionSpec()) for intentional replication",
                        scope=f"{cls.name}._build_programs",
                        detail=target.attr))
    return findings


def _find_wrap_call(node: ast.AST) -> ast.Call | None:
    """The ``<tracker>.wrap(...)`` call inside an expression like
    ``ct.wrap(jax.jit(fn), "name", timer=...)``."""
    for sub in ast.walk(node):
        if (isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "wrap"):
            return sub
    return None


@register_pass(("KUKE015",))
def check_program_timers(sources: Sequence[SourceFile],
                         package_root: str) -> list[Finding]:
    """Every jitted program must register with the program-timer seam."""
    findings: list[Finding] = []
    for src in sources:
        if not src.rel.endswith(ENGINE_FILE_SUFFIX):
            continue
        for cls in src.tree.body:
            if not (isinstance(cls, ast.ClassDef)
                    and cls.name == ENGINE_CLASS):
                continue
            build = next(
                (m for m in cls.body if isinstance(m, ast.FunctionDef)
                 and m.name == "_build_programs"), None)
            if build is None:
                continue
            for node in ast.walk(build):
                if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                    continue
                target = node.targets[0]
                if not (is_self_attr(target)
                        and target.attr in JITTED_PROGRAMS):
                    continue
                wrap_call = _find_wrap_call(node.value)
                if wrap_call is None or not any(
                        kw.arg == "timer" for kw in wrap_call.keywords):
                    findings.append(Finding(
                        "KUKE015", src.rel, node.lineno,
                        f"jitted program {target.attr} is built without a "
                        f"timer= registration on its CompileTracker.wrap: "
                        f"its dispatches are invisible to the per-program "
                        f"wall-time/MFU gauges and the flight recorder — "
                        f"wrap it with timer=tm.track(\"<program>\")",
                        scope=f"{cls.name}._build_programs",
                        detail=target.attr))
    return findings


def _called_program(node: ast.Call) -> str | None:
    """``self._prog(...)`` or ``self._prog.lower(...)`` -> ``_prog``."""
    f = node.func
    if is_self_attr(f) and f.attr in JITTED_PROGRAMS:
        return f.attr
    if (isinstance(f, ast.Attribute) and f.attr == "lower"
            and is_self_attr(f.value) and f.value.attr in JITTED_PROGRAMS):
        return f.value.attr
    return None
