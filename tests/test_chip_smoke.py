"""chip_smoke.py and the process rules of the chip path, rehearsed on the
CPU: the smoke's two legs at tiny sizes, who may hold a JAX backend, where
the compile cache goes, and what the launcher answers to a shape it cannot
give chips to.  What only a chip can show is ``python chip_smoke.py``
itself (README, "On the chip")."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

TINY = ["--layers", "1", "--d-model", "32", "--heads", "2", "--d-ff", "64",
        "--vocab", "128", "--seq", "32", "--batch", "2", "--timed-steps", "1",
        "--max-batch", "2", "--prompt-lens", "5,18", "--max-new", "3",
        "--check-tokens", "3"]


def run(argv, timeout=240, **env_changes):
    env = {k: v for k, v in os.environ.items()
           # the conftest's 8 virtual devices are this process's, not the
           # children's
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_changes)
    return subprocess.run(argv, capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=timeout)


def test_cpu_rehearsal_both_legs_and_a_parent_off_jax():
    """Both legs through the real entry points; exit 0; the last line is
    the contract's and reports the CPU it ran on; and the process that
    launched the legs never imported JAX."""
    code = ("import sys, chip_smoke\n"
            "rc = chip_smoke.main(sys.argv[1:])\n"
            "print('jax in parent:', 'jax' in sys.modules, file=sys.stderr)\n"
            "sys.exit(rc)\n")
    r = run([sys.executable, "-c", code, "--cpu"] + TINY)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "jax in parent: False" in r.stderr
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    facts = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in lines[:-1]}
    train, serve = facts["train"], facts["serve"]
    assert train["steps"] == 12 and train["pulse"]["samples"] == 1
    assert train["loss_last"] < train["loss_first"]
    assert train["host_transport"] in ("native", "python")
    assert serve["tokens_returned"] == {"r0": 3, "r1": 3}
    assert serve["checked_tokens"] == 3
    # the CPU rehearsal keeps the compile cache off
    assert train["compile_cache"] == serve["compile_cache"] == {
        "dir": None, "hits": 0, "misses": 0}


def test_without_a_chip_it_fails_and_prints_no_result():
    r = run([sys.executable, SMOKE] + TINY, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "Unable to initialize backend 'tpu'" in r.stderr


def test_launcher_parent_initialises_no_backend():
    """A chip belongs to one process: the launcher imports jax (the
    package does) but must leave every backend to its workers."""
    code = ("import sys\n"
            "from kungfu_tpu.runner.cli import main\n"
            "rc = main(['-np', '1', '-q', sys.executable, '-c', 'pass'])\n"
            "from jax._src import xla_bridge\n"
            "print('backends:', xla_bridge.backends_are_initialized())\n"
            "sys.exit(rc)\n")
    r = run([sys.executable, "-c", code])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "backends: False" in r.stdout


@pytest.mark.parametrize("preset", [None, "/somewhere/else"],
                         ids=["unset", "set"])
def test_compile_cache_placement(monkeypatch, preset):
    """Unset: one fixed directory inside the checkout.  Set: JAX reads
    the variable itself (at its import) and the helper sets nothing."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from kungfu_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if preset:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", preset)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        returned = enable_compile_cache()
        configured = jax.config.jax_compilation_cache_dir
    finally:  # under the tests the cache stays off
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
    if preset:
        assert returned == preset and configured == before
    else:
        assert returned == configured == os.path.join(REPO, ".jax_cache")


def test_launcher_refuses_two_tpu_workers_on_one_host(tmp_path):
    """Each would reach for all of the host's chips and the second would
    fail or hang; the launcher says so before it starts anything."""
    marker = tmp_path / "started"
    r = run([sys.executable, "-m", "kungfu_tpu", "-np", "2",
             "-backend", "tpu", sys.executable, "-c",
             f"open({str(marker)!r}, 'w')"], timeout=60)
    assert r.returncode != 0
    assert "ONE worker process per host" in r.stderr
    assert not marker.exists()
