"""The persistent compile cache rule (runtime/serving_cell.py).

Where JAX_COMPILATION_CACHE_DIR is set the program sets no directory in code
(JAX reads the variable itself) and creates nothing under it; where it is
not, the cache lives at ONE fixed path inside the checkout — the path is part
of every entry's key, so a directory that moves (a hash, $HOME on a machine
that is new every run, a temp name, a pid) never hits.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from kukeon_tpu.runtime import serving_cell

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them (the config
    is process-global; the test run's own cache must not move)."""
    seen: dict[str, object] = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, val: seen.__setitem__(key, val))
    return seen


def test_env_var_set_means_code_sets_no_directory(tmp_path, monkeypatch,
                                                  config_updates):
    where = tmp_path / "operator-cache"
    where.mkdir()
    monkeypatch.setenv(serving_cell.CACHE_DIR_ENV, str(where))
    serving_cell.enable_compilation_cache()
    assert "jax_compilation_cache_dir" not in config_updates
    assert serving_cell.compilation_cache_dir() == str(where)
    assert list(where.iterdir()) == []       # no sub-directory under it
    # The min-compile-time knob is not a directory; it is still applied.
    assert "jax_persistent_cache_min_compile_time_secs" in config_updates


def test_env_var_unset_means_the_fixed_in_checkout_path(monkeypatch,
                                                        config_updates):
    monkeypatch.delenv(serving_cell.CACHE_DIR_ENV, raising=False)
    serving_cell.enable_compilation_cache()
    want = os.path.join(REPO, ".jax_cache")
    assert config_updates["jax_compilation_cache_dir"] == want
    assert serving_cell.compilation_cache_dir() == want
    assert os.path.isdir(want)


def test_two_processes_name_the_same_path(tmp_path):
    """Another pid, another cwd, another $HOME: the same directory."""
    code = ("from kukeon_tpu.runtime import serving_cell as s; "
            "print(s.compilation_cache_dir())")
    procs = []
    for i in range(2):
        home = tmp_path / f"home{i}"
        home.mkdir()
        env = {k: v for k, v in os.environ.items()
               if k != serving_cell.CACHE_DIR_ENV}
        env.update({"HOME": str(home), "PYTHONPATH": REPO,
                    "JAX_PLATFORMS": "cpu"})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=str(home),
            stdout=subprocess.PIPE, text=True))
    paths = []
    for p in procs:
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0
        paths.append(out.strip().splitlines()[-1])
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")


def test_bust_empties_whichever_directory_is_in_force(tmp_path, monkeypatch):
    where = tmp_path / "operator-cache"
    (where / "sub").mkdir(parents=True)
    (where / "entry-1").write_bytes(b"x")
    (where / "sub" / "entry-2").write_bytes(b"y")
    monkeypatch.setenv(serving_cell.CACHE_DIR_ENV, str(where))
    assert serving_cell._bust_compilation_cache() is True
    assert where.is_dir() and list(where.iterdir()) == []
    assert serving_cell._bust_compilation_cache() is False   # nothing left


def test_cache_that_cannot_be_set_up_is_reported(tmp_path, monkeypatch,
                                                 config_updates, capsys):
    """No silent `except: pass`: an unusable cache path costs every boot a
    cold compile, so it is said on stderr."""
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    monkeypatch.delenv(serving_cell.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(serving_cell, "_CHECKOUT_CACHE_DIR",
                        str(blocker / ".jax_cache"))
    serving_cell.enable_compilation_cache()
    assert "cannot be set up" in capsys.readouterr().err
    assert "jax_compilation_cache_dir" not in config_updates
