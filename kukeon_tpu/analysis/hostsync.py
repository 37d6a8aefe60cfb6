"""KUKE001/KUKE002 — host-sync discipline in the serving engine hot path.

The decode roofline contract (PR 1, enforced dynamically by
``test_decode_host_sync_budget``): every blocking device→host readback in
the engine goes through ``ServingEngine._fetch`` and every host→device
array upload through ``_upload``, so the ≤1-blocking-transfer-per-chunk
budget is *countable*. This pass makes the routing itself a lint error —
a raw transfer in a hot-path method is flagged at review time instead of
showing up as a budget-test failure (or worse, a latency regression the
budget test's snapshot happens to miss).

- **KUKE001** (device→host): ``np.asarray``/``np.array`` on a
  device-tainted value, ``jax.device_get(...)``, ``.item()``,
  ``.block_until_ready()``, and ``int()``/``float()``/``bool()`` coercion
  of a device-tainted value, inside a hot-path method, outside ``_fetch``.
- **KUKE002** (host→device): ``jnp.asarray``/``jnp.array``/
  ``jax.device_put`` inside a hot-path method, outside ``_upload`` —
  uploads must route through the counting seam even when cheap, or the
  budget tests undercount and the dirty-flag discipline silently erodes.

Device taint is a per-method forward propagation: results of the engine's
jitted programs (and ``self.state``/``self.params``/device caches, and
``jnp.*`` array results) are device values; ``self._fetch(...)`` results
and ``np.*`` results are host values; unknown stays unflagged — the pass
prefers false negatives over noise, with the runtime budget test as the
dynamic backstop. Metadata access (``x.shape``/``x.dtype``/``x.size``…)
never counts as a transfer.
"""

from __future__ import annotations

import ast
from typing import Sequence

from kukeon_tpu.analysis.core import (
    Finding, SourceFile, is_self_attr, register_pass,
)

ENGINE_FILE_SUFFIX = "serving/engine.py"
ENGINE_CLASS = "ServingEngine"

# The transfer seams themselves: raw transfer primitives are their job.
SEAM_METHODS = ("_fetch", "_upload")

# Methods on the submit->prefill->decode->emit path (plus warmup, which
# dispatches real chunks): the scope where a stray transfer costs a link
# round trip per request or per chunk.
HOT_PATH_METHODS = frozenset({
    "submit", "step", "_step", "warmup", "generate", "_loop",
    "_dispatch_prefill", "_dispatch_prefill_dense", "_dispatch_prefill_paged",
    "_dispatch_decode_chunk",
    "_flush_inflight", "_emit", "_release_slot", "_preempt_slot",
    "_sampling_dev_arrays", "_bt_dev_array", "_ensure_decode_pages",
    "_prefix_lookup", "_prefix_store", "_prefix_lookup_paged",
    "_prefix_store_paged", "_reclaim_prefix_pages", "_chunk_size",
    "_pop_waiting", "_sweep_cancelled",
})

# The engine's jitted programs: their results are device values.
JITTED_PROGRAMS = frozenset({
    "_prefill", "_prefill_ext", "_insert", "_decode_chunk",
    "_gather_block", "_insert_paged", "_decode_chunk_paged",
})

# Always-device engine attributes.
DEVICE_SELF_ATTRS = frozenset({
    "state", "params", "_bt_dev", "_sampling_dev",
})

# Attribute reads that are static metadata, never a transfer.
METADATA_ATTRS = frozenset({
    "shape", "ndim", "size", "dtype", "nbytes", "itemsize", "sharding",
})

# jnp names that are dtype constructors / free functions on device values,
# not transfers.
JNP_UPLOADS = frozenset({"asarray", "array"})


def _is_metadata(node: ast.AST) -> bool:
    while isinstance(node, ast.Subscript):
        node = node.value
    return isinstance(node, ast.Attribute) and node.attr in METADATA_ATTRS


class _Taint:
    """Per-method device-taint set over local names."""

    def __init__(self) -> None:
        self.device: set[str] = set()

    def expr_is_device(self, node: ast.AST) -> bool:
        if _is_metadata(node):
            return False
        if is_self_attr(node) and node.attr in DEVICE_SELF_ATTRS:
            return True
        if isinstance(node, ast.Call):
            # Results of the counting seams have known sides regardless of
            # their argument taint: _fetch returns host numpy, _upload a
            # device array. np.* construct host arrays; jnp.* device ones.
            if is_self_attr(node.func, "_fetch"):
                return False
            if is_self_attr(node.func, "_upload"):
                return True
            base, _attr = _call_name(node)
            if base == "np":
                return False
            if base == "jnp":
                return True
        if _jitted_call(node) is not None:
            return True
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in self.device:
                if not _is_metadata_path(node, sub):
                    return True
            if is_self_attr(sub) and sub.attr in DEVICE_SELF_ATTRS:
                if not _is_metadata_path(node, sub):
                    return True
        return False


def _is_metadata_path(root: ast.AST, target: ast.AST) -> bool:
    """True when ``target`` is only reached through a metadata attribute
    access within ``root`` (e.g. the ``x`` of ``x.shape[0]``)."""
    for sub in ast.walk(root):
        if isinstance(sub, ast.Attribute) and sub.attr in METADATA_ATTRS:
            for inner in ast.walk(sub.value):
                if inner is target:
                    return True
    return False


def _jitted_call(node: ast.AST) -> str | None:
    """Name of the jitted program when ``node`` is ``self._prog(...)``."""
    if (isinstance(node, ast.Call)
            and is_self_attr(node.func)
            and node.func.attr in JITTED_PROGRAMS):
        return node.func.attr
    return None


def _call_name(node: ast.Call) -> tuple[str | None, str | None]:
    """(module-ish base, attr) for ``base.attr(...)`` / (None, name)."""
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        return f.value.id, f.attr
    if isinstance(f, ast.Name):
        return None, f.id
    return None, None


def _seed_and_check(method: ast.FunctionDef, cls_name: str,
                    rel: str) -> list[Finding]:
    """Two passes over the statements: propagate taint, then flag. A single
    sweep in statement order is enough for straight-line dataflow; the
    second sweep catches names tainted later in a loop body."""
    taint = _Taint()
    findings: list[Finding] = []
    scope = f"{cls_name}.{method.name}"

    def assign_taint(target: ast.AST, value_is_device: bool) -> None:
        if not value_is_device:
            return
        if isinstance(target, ast.Name):
            taint.device.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                assign_taint(elt, True)

    def propagate(node: ast.AST) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign):
                dev = taint.expr_is_device(sub.value)
                for t in sub.targets:
                    assign_taint(t, dev)
            elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
                if sub.value is not None and taint.expr_is_device(sub.value):
                    assign_taint(sub.target, True)
            elif isinstance(sub, ast.For):
                if taint.expr_is_device(sub.iter):
                    assign_taint(sub.target, True)
            elif isinstance(sub, ast.withitem) and sub.optional_vars:
                if taint.expr_is_device(sub.context_expr):
                    assign_taint(sub.optional_vars, True)

    def flag(node: ast.Call) -> None:
        base, attr = _call_name(node)
        args = node.args
        # --- device→host (KUKE001) ------------------------------------
        if attr == "item" and not args and isinstance(node.func,
                                                      ast.Attribute):
            findings.append(Finding(
                "KUKE001", rel, node.lineno,
                f"raw device→host transfer `.item()` in hot-path "
                f"{scope}; route the readback through self._fetch",
                scope=scope, detail="item"))
            return
        if attr == "block_until_ready" and isinstance(node.func,
                                                      ast.Attribute):
            findings.append(Finding(
                "KUKE001", rel, node.lineno,
                f"`.block_until_ready()` in hot-path {scope} blocks the "
                f"driver on the device; route through self._fetch",
                scope=scope, detail="block_until_ready"))
            return
        if base == "jax" and attr == "device_get":
            findings.append(Finding(
                "KUKE001", rel, node.lineno,
                f"raw `jax.device_get` in hot-path {scope}; route the "
                f"readback through self._fetch",
                scope=scope, detail="device_get"))
            return
        if (base == "np" and attr in ("asarray", "array") and args
                and taint.expr_is_device(args[0])):
            findings.append(Finding(
                "KUKE001", rel, node.lineno,
                f"`np.{attr}` on a device value in hot-path {scope} is a "
                f"blocking uncounted readback; route through self._fetch",
                scope=scope, detail=f"np.{attr}"))
            return
        if (base is None and attr in ("int", "float", "bool") and args
                and taint.expr_is_device(args[0])):
            findings.append(Finding(
                "KUKE001", rel, node.lineno,
                f"`{attr}()` coercion of a device value in hot-path "
                f"{scope} is a blocking uncounted readback; fetch the "
                f"array through self._fetch first",
                scope=scope, detail=f"coerce.{attr}"))
            return
        # --- host→device (KUKE002) ------------------------------------
        if base == "jnp" and attr in JNP_UPLOADS:
            findings.append(Finding(
                "KUKE002", rel, node.lineno,
                f"raw `jnp.{attr}` upload in hot-path {scope}; route the "
                f"upload through self._upload so the transfer budget "
                f"counts it",
                scope=scope, detail=f"jnp.{attr}"))
            return
        if base == "jax" and attr == "device_put":
            findings.append(Finding(
                "KUKE002", rel, node.lineno,
                f"raw `jax.device_put` upload in hot-path {scope}; route "
                f"through self._upload",
                scope=scope, detail="device_put"))

    propagate(method)
    propagate(method)   # second sweep: loop-carried taint
    for sub in ast.walk(method):
        if isinstance(sub, ast.Call):
            flag(sub)
    return findings


@register_pass(("KUKE001", "KUKE002"))
def check_host_sync(sources: Sequence[SourceFile],
                    package_root: str) -> list[Finding]:
    findings: list[Finding] = []
    for src in sources:
        if not src.rel.endswith(ENGINE_FILE_SUFFIX):
            continue
        for node in src.tree.body:
            if not (isinstance(node, ast.ClassDef)
                    and node.name == ENGINE_CLASS):
                continue
            for meth in node.body:
                if not isinstance(meth, ast.FunctionDef):
                    continue
                if meth.name in SEAM_METHODS:
                    continue
                if meth.name not in HOT_PATH_METHODS:
                    continue
                findings.extend(_seed_and_check(meth, node.name, src.rel))
    return findings


# --- KUKE012: KV handoff transfer discipline ---------------------------------
#
# The disaggregated prefill/decode handoff moves whole KV blocks between
# cells — by far the largest per-request transfers in the tree. Every byte
# must cross the device boundary through the counted seams
# (``self._fetch`` / ``self._upload``, or an explicit
# ``sanitize.blocking(...)``-marked section), or the handoff's cost is
# invisible to ``sync_stats``, the ``kukeon_engine_host_sync_*``
# exposition, AND the kukesan blocking-under-hot-lock checks. This pass
# scopes to export/import-named methods in the serving engine and cell —
# the code that owns handoff bytes — and flags raw transfer primitives
# there; the generic hot-path discipline stays KUKE001/002's job.

import re as _re

HANDOFF_FILE_SUFFIXES = (ENGINE_FILE_SUFFIX, "runtime/serving_cell.py")
# Methods/functions owning handoff bytes: anything whose name carries an
# export/import marker (``kv_export``, ``_dispatch_prefill_export``,
# ``_finish_export``, ``_dispatch_import``, ``kv_import_stream``...).
# ``pack_kv``/``unpack_kv`` (pure host serialization) are covered too —
# a device transfer has no business appearing in them at all.
HANDOFF_NAME_RE = _re.compile(
    r"(^|_)(export|import)(ed)?(_|$)|(^|_)kv(_|$)")


def _handoff_findings(fn: ast.FunctionDef, scope: str,
                      rel: str) -> list[Finding]:
    taint = _Taint()
    findings: list[Finding] = []

    def flag(node: ast.Call) -> None:
        base, attr = _call_name(node)
        if base == "jax" and attr in ("device_get", "device_put"):
            findings.append(Finding(
                "KUKE012", rel, node.lineno,
                f"raw `jax.{attr}` in KV handoff code ({scope}); handoff "
                f"bytes must move through the counted transfer seams "
                f"(self._fetch / self._upload / sanitize.blocking)",
                scope=scope, detail=f"jax.{attr}"))
            return
        if base == "jnp" and attr in JNP_UPLOADS:
            findings.append(Finding(
                "KUKE012", rel, node.lineno,
                f"raw `jnp.{attr}` upload in KV handoff code ({scope}); "
                f"route the block through self._upload so the handoff's "
                f"transfer cost is counted",
                scope=scope, detail=f"jnp.{attr}"))
            return
        if (base == "np" and attr in ("asarray", "array") and node.args
                and taint.expr_is_device(node.args[0])):
            findings.append(Finding(
                "KUKE012", rel, node.lineno,
                f"`np.{attr}` on a device value in KV handoff code "
                f"({scope}) is a blocking uncounted readback; route the "
                f"block through self._fetch",
                scope=scope, detail=f"np.{attr}"))

    # Reuse the host-sync taint model (device values = jitted program
    # results, device self attrs, jnp results) with a minimal assignment
    # propagation — handoff methods are straight-line.
    def propagate(node: ast.AST) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign):
                dev = taint.expr_is_device(sub.value)
                for tgt in sub.targets:
                    if dev and isinstance(tgt, ast.Name):
                        taint.device.add(tgt.id)
                    elif dev and isinstance(tgt, (ast.Tuple, ast.List)):
                        for elt in tgt.elts:
                            if isinstance(elt, ast.Name):
                                taint.device.add(elt.id)

    propagate(fn)
    propagate(fn)   # second sweep: loop-carried taint
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Call):
            flag(sub)
    return findings


@register_pass(("KUKE012",))
def check_handoff_transfers(sources: Sequence[SourceFile],
                            package_root: str) -> list[Finding]:
    findings: list[Finding] = []
    for src in sources:
        if not any(src.rel.endswith(sfx) for sfx in HANDOFF_FILE_SUFFIXES):
            continue
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for meth in node.body:
                if not isinstance(meth, ast.FunctionDef):
                    continue
                if meth.name in SEAM_METHODS:
                    continue
                if not HANDOFF_NAME_RE.search(meth.name):
                    continue
                findings.extend(_handoff_findings(
                    meth, f"{node.name}.{meth.name}", src.rel))
        for node in src.tree.body:
            if (isinstance(node, ast.FunctionDef)
                    and HANDOFF_NAME_RE.search(node.name)):
                findings.extend(_handoff_findings(node, node.name, src.rel))
    return findings
