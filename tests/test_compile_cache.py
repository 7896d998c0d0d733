"""Where the persistent compile cache lives (zoo_tpu/common/compile_cache.py).

The rule: ``JAX_COMPILATION_CACHE_DIR`` set -> the program sets nothing
(jax reads it); unset -> ``<checkout>/.jax_cache``. Never a path made
from a temp name, a pid or the clock: the path is part of the cache
key, so a directory that moves never hits.
"""

import inspect
import os
import re

import pytest

import jax

from zoo_tpu.common import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def jax_cache_config():
    """Leave jax's own setting as this test found it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_placed_from_outside_sets_nothing(monkeypatch, jax_cache_config):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/somewhere/durable")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.ensure_compile_cache() == "/somewhere/durable"
    assert calls == []
    assert os.environ[compile_cache.CACHE_DIR_ENV] == "/somewhere/durable"


def test_unset_means_the_fixed_in_checkout_path(monkeypatch,
                                                jax_cache_config):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.DEFAULT_CACHE_DIR == want
    assert compile_cache.ensure_compile_cache() == want
    # jax, already imported, was told; processes spawned from here (and
    # a jax imported later) read it from the environment
    assert jax.config.jax_compilation_cache_dir == want
    assert os.environ[compile_cache.CACHE_DIR_ENV] == want
    # a second call finds it placed and changes nothing
    assert compile_cache.ensure_compile_cache() == want


def test_no_path_from_temp_pid_or_time():
    src = inspect.getsource(compile_cache)
    for word in ("tempfile", "mkdtemp", "getpid", "time", "uuid",
                 "random"):
        assert not re.search(rf"\b{word}\b", src), word
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_one_function_owns_the_setting():
    """Every entry that jits calls the function, and no other file
    touches jax's cache-dir option."""
    def read(rel):
        with open(os.path.join(REPO, rel)) as f:
            return f.read()

    for rel in ("zoo_tpu/orca/common.py", "zoo_tpu/serving/replica.py",
                "zoo_tpu/serving/run.py", "chip_smoke.py"):
        assert "ensure_compile_cache()" in read(rel), rel
    offenders = []
    for top in ("zoo_tpu", "scripts"):
        for root, _, files in os.walk(os.path.join(REPO, top)):
            for name in files:
                rel = os.path.relpath(os.path.join(root, name), REPO)
                if name.endswith(".py") \
                        and rel != "zoo_tpu/common/compile_cache.py" \
                        and "jax_compilation_cache_dir" in read(rel):
                    offenders.append(rel)
    for rel in ("chip_smoke.py", "__graft_entry__.py"):
        if "jax_compilation_cache_dir" in read(rel):
            offenders.append(rel)
    assert offenders == []
