"""Start-up rules a chip imposes: a chip belongs to one process, so importing
the package (or a launcher / bench parent built on it) must initialise no
backend; the compile cache must be placeable from outside; and asking for a
TPU that is not there must fail, not hand back another device."""

import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_initialises_no_backend():
    """A fresh process that imports the package, seeds it, and imports what
    the launcher and bench.py's parent import still has no backend — the
    chip stays free for the child it starts."""
    code = (
        "import paddle_tpu as paddle\n"
        "paddle.seed(7)\n"
        "import paddle_tpu.distributed.launch\n"
        "import benchmarks.raw_resnet50, benchmarks.raw_bert\n"
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from paddle_tpu.observability import programs\n"
        "print(programs.ledger().builds()['phases']['startup.import'])\n"
        "print(len(jax._src.monitoring.get_event_duration_listeners()))\n"
        "print(list(xla_bridge._backends))\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr
    phase, listeners, backends, cache_dir = r.stdout.strip().splitlines()[-4:]
    assert backends == "[]"
    # the import is the record's first phase, and the one build listener is
    # registered by it: the process's first program will be counted
    phase = eval(phase)     # a dict of numbers this test's child printed
    assert phase["n"] == 1 and phase["seconds"] == phase["self_s"] > 0
    assert listeners == "1"
    assert cache_dir == os.path.join(REPO, ".jax_cache")


def test_seed_is_deterministic_with_a_lazy_key():
    paddle.seed(11)
    a = jax.random.key_data(paddle.framework.random.next_key())
    state = paddle.get_rng_state()
    b = jax.random.key_data(paddle.framework.random.next_key())
    paddle.seed(11)
    assert (jax.random.key_data(paddle.framework.random.next_key()) == a).all()
    paddle.set_rng_state(state)
    assert (jax.random.key_data(paddle.framework.random.next_key()) == b).all()


def test_compile_cache_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the code sets no directory.  Unset:
    one fixed, git-ignored path inside the checkout."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        paddle._configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        paddle._configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_set_device_never_substitutes_a_device():
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.set_device("tpu")
    with pytest.raises(ValueError, match="8 cpu device"):
        paddle.set_device("cpu:8")
    assert paddle.get_device() == before
    assert paddle.set_device("cpu:1").jax_device() == jax.devices("cpu")[1]
    jax.config.update("jax_default_device", None)
    paddle.device._current = None
