"""The chip smoke script and the launch helpers it relies on, on the CPU.

`chip_smoke.py` itself needs a TPU; here it must refuse to run, and its
one-chip phases are rehearsed at a tiny size with the Pallas kernels
under the interpreter, so the script's logic cannot rot between chip
runs.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(extra)
    return env


def test_smoke_refuses_without_a_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_smoke_phases_rehearse_on_cpu_interpreter(capsys):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from repro.launch.serve import testbed_config

    chip_smoke.run_one_chip(testbed_config(layers=2), seq_len=16, steps=5,
                            kernel_backend="pallas_interpret")
    out = capsys.readouterr().out
    assert out.count("pallas-vs-ref") == 2 * len(chip_smoke.BUCKETS) + 2
    for what in ("batched B=32", "Engine", "SplitEE-S", "scan B=32"):
        assert f"pallas_interpret+fused] {what}" in out


_CACHE_PROBE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    if {compile}:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
    print(path)
    print(jax.config.jax_compilation_cache_dir)
""")


def test_compile_cache_follows_the_environment(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=True)],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir()), "no cache entries where the env says"


def test_compile_cache_defaults_to_the_checkout():
    # no compile here: that would write into the checkout's cache
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=False)],
        env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    want = os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want] * 2


@pytest.mark.parametrize("platforms,chips,refused", [
    ("", 1, True), ("tpu", 4, True), ("cpu", 4, False), ("", 0, False)])
def test_cluster_refuses_to_share_a_chip(monkeypatch, platforms, chips,
                                         refused):
    from jax._src import hardware_utils

    from repro.serving.distributed import refuse_shared_chip

    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (chips, None))
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if refused:
        with pytest.raises(RuntimeError, match="one chip per process"):
            refuse_shared_chip(2)
    else:
        refuse_shared_chip(2)
    refuse_shared_chip(1)                   # one worker never shares
    refuse_shared_chip(2, {"JAX_PLATFORMS": "cpu"})
