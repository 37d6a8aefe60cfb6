"""The documents against the tree, in the directions no kukelint rule holds.

kukelint KUKE008 holds code -> README for metric families and KUKE007 holds
the fault points both ways. These tests hold the rest: what README.md,
PERF.md and tools/check.sh NAME (paths, metric families, environment
variables, `kuke` verbs, span and counter names) exists in the tree, and the
environment variables the tree reads are documented. Each test reports the
whole list of misses. A miss is fixed in the document (or the code), not by
widening a list here.
"""

from __future__ import annotations

import ast
import functools
import itertools
import os
import re
import shlex

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A repository path as a document writes it: relative to the checkout, or
# to the package / the benchmark ("obs/spans.py", "layer_metrics/...").
PATH_BASES = ("", "kukeon_tpu", "benchmark")
PATH_EXTS = (".py", ".md", ".json", ".jsonl", ".sh", ".toml", ".yml", ".yaml",
             ".cc", ".cpp")
PATH_RE = re.compile(r"[A-Za-z0-9_.\-]+(?:/[A-Za-z0-9_.\-]+)*/?\Z")

def _read(*parts: str) -> str:
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return f.read()


def _py_files(*roots: str):
    for root in roots:
        full = os.path.join(ROOT, root)
        if os.path.isfile(full):
            yield full
            continue
        for d, _dirs, files in os.walk(full):
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(d, name)


@functools.lru_cache(maxsize=None)
def _string_constants(*roots: str) -> frozenset[str]:
    """Every string constant (f-string pieces included) in the Python
    files under ``roots``."""
    out: set[str] = set()
    for path in _py_files(*roots):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                out.add(node.value)
    return frozenset(out)


def _inline_code(text: str) -> list[str]:
    """The `inline code` spans of a markdown text, fenced blocks left out."""
    prose = re.sub(r"```.*?```", "", text, flags=re.S)
    return re.findall(r"`([^`\n]+)`", prose)


def _fenced_lines(text: str) -> list[str]:
    lines: list[str] = []
    for block in re.findall(r"```[a-z]*\n(.*?)```", text, flags=re.S):
        lines.extend(block.splitlines())
    return lines


def _resolves(path: str, *more_bases: str) -> bool:
    return any(os.path.exists(os.path.join(ROOT, base, path))
               for base in PATH_BASES + more_bases)


def _looks_like_repo_path(token: str) -> bool:
    if not PATH_RE.match(token) or token.startswith(("/", ".", "-")):
        return False
    first = token.split("/", 1)[0]
    return (token.endswith(PATH_EXTS) or token.endswith("/")
            or ("/" in token and os.path.exists(os.path.join(ROOT, first))))


def test_every_path_the_readme_names_exists():
    misses = sorted({
        tok for tok in (t.split(":")[0].rstrip(",.") for t in
                        _inline_code(_read("README.md")))
        if _looks_like_repo_path(tok) and not _resolves(tok)})
    assert not misses, f"README.md names paths that do not exist: {misses}"


def _metric_reference_families() -> list[str]:
    text = _read("README.md")
    table = text[text.index("### Metric reference"):]
    table = table[:table.index("\n## ")]
    fams = re.findall(r"^\| `(kukeon_[a-z0-9_]+)` \|", table, flags=re.M)
    assert len(fams) > 50, "the Metric reference table was not found"
    return fams


def test_every_family_in_the_metric_reference_is_in_the_code():
    """README -> code: a family deleted from the package and left in the
    table fails here (KUKE008 holds the other direction)."""
    consts = _string_constants("kukeon_tpu")
    misses = sorted(f for f in _metric_reference_families()
                    if f not in consts)
    assert not misses, ("README.md's Metric reference names families no "
                        f"string constant under kukeon_tpu/ spells: {misses}")


# A name, or the fixed head of a family of names the runner builds
# (`KUKEON_SECRET_<NAME>` is spelt "KUKEON_SECRET_" on both sides).
ENV_RE = re.compile(r"KUKEON_[A-Z0-9]+(?:_[A-Z0-9]+)*_?")


def _env_names(*roots: str) -> set[str]:
    return {c for c in _string_constants(*roots) if ENV_RE.fullmatch(c)}


def test_every_variable_the_readme_names_is_read_by_the_tree():
    read = _env_names("kukeon_tpu", "chip_smoke.py", "tests/conftest.py")
    misses = sorted(set(ENV_RE.findall(_read("README.md"))) - read)
    assert not misses, ("README.md names KUKEON_* variables that neither "
                        "kukeon_tpu/, chip_smoke.py nor tests/conftest.py "
                        f"reads: {misses}")


def test_every_variable_the_tree_reads_is_in_the_readme():
    named = set(ENV_RE.findall(_read("README.md")))
    misses = sorted(_env_names("kukeon_tpu", "chip_smoke.py") - named)
    assert not misses, ("the tree reads KUKEON_* variables README.md does "
                        f"not name: {misses}")


def test_every_kuke_verb_the_readme_shows_parses():
    """Inline `kuke <verb> ...` spans must name a verb of the CLI's own
    parser; whole `kuke ...` command lines in fenced blocks must parse."""
    from kukeon_tpu.runtime import cli

    parser = cli.build_parser()
    verbs = set(next(a for a in parser._actions
                     if a.dest == "cmd").choices)
    text = _read("README.md")
    misses = sorted({
        f"`{span}`" for span in _inline_code(text)
        for m in [re.match(r"kuke ([a-z][a-z-]*)", span)]
        if m and m.group(1) not in verbs})
    for line in _fenced_lines(text):
        m = re.match(r"\s*(?:\$ )?(?:kuke|\$K) (.*)", line)
        if not m:
            continue
        argv = itertools.takewhile(          # up to a here-document
            lambda a: not a.startswith("<<"),
            shlex.split(m.group(1), comments=True))
        try:
            parser.parse_args(list(argv))
        except SystemExit:
            misses.append(line.strip())
    assert not misses, f"README.md shows kuke commands that do not parse: {misses}"


def test_every_span_and_counter_in_perf_md_is_in_the_program():
    """PERF.md section 3's second table (the program's own spans and
    counters) against the names the package spells."""
    text = _read("PERF.md")
    start = text.index("The program's own spans and counters")
    table = text[start:text.index("\n## 4.", start)]
    names: set[str] = set()
    for row in re.findall(r"^\| (.*?) \|", table, flags=re.M):
        for tok in re.findall(r"`([^`]+)`", row):
            tok = tok.split("{")[0]
            if re.fullmatch(r"(?:engine|cell)\.[a-z_]+|kukeon_[a-z0-9_]+", tok):
                names.add(tok)
    assert len(names) > 10, "PERF.md's span table was not found"
    misses = sorted(names - _string_constants("kukeon_tpu"))
    assert not misses, ("PERF.md section 3 names spans or counters that no "
                        f"string constant under kukeon_tpu/ spells: {misses}")


def test_every_path_check_sh_names_exists():
    """Fails on a check.sh that still compiles or runs a deleted file."""
    words: set[str] = set()
    for line in _read("tools", "check.sh").splitlines():
        line = line.split("#", 1)[0]
        words.update(re.findall(r"[A-Za-z0-9_.\-/]+", line))
    top = set(os.listdir(ROOT))
    misses = sorted(
        w for w in words
        if (w.endswith(PATH_EXTS) or w.split("/", 1)[0] in top)
        and not w.startswith(("/", "-", "."))
        and not _resolves(w, "tools"))
    assert not misses, f"tools/check.sh names paths that do not exist: {misses}"
