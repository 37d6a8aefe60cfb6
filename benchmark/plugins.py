"""Finds the files that belong to one configuration, mix or metric by name.

A generator, a per-layer reader, an operation count, a reference or a launcher
is one file ``<kind>/<name>.py`` beside the configuration that uses it
(``pkg_dir``, the directory that holds ``configs/`` and ``traffic/``), or else
in this directory.
So a later PR adds a file and an entry of BENCHMARK.json, and edits nothing.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def one_line(err: BaseException, most: int = 600) -> str:
    """What ended a run, for a line of a record: a SystemExit's text, another
    exception's type and message."""
    what = str(err.code) if isinstance(err, SystemExit) \
        else f"{type(err).__name__}: {err}"
    return " ".join(what.split())[:most]


def load(kind: str, name: str, pkg_dir: str = HERE):
    for base in dict.fromkeys((os.path.abspath(pkg_dir), HERE)):
        path = os.path.join(base, kind, name + ".py")
        if os.path.exists(path):
            mod_name = f"benchmark_plugin_{abs(hash(base))}_{kind}_{name}"
            if mod_name in sys.modules:
                return sys.modules[mod_name]
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            return mod
    raise SystemExit(f"benchmark: no {kind}/{name}.py under {pkg_dir} or "
                     f"{HERE}. No result.")
