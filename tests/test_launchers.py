"""Launcher entrypoints run end to end on a dev host (reduced configs)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(mod, *args):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-m", mod, *args], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_launcher(tmp_path):
    out = _run("repro.launch.train", "--arch", "qwen3-1.7b", "--reduce",
               "--steps", "12", "--batch", "4", "--seq", "32",
               "--checkpoint-every", "6",
               "--workdir", str(tmp_path / "w"))
    assert "[train] done" in out
    # relaunch resumes from the checkpoint
    out2 = _run("repro.launch.train", "--arch", "qwen3-1.7b", "--reduce",
                "--steps", "12", "--batch", "4", "--seq", "32",
                "--checkpoint-every", "6",
                "--workdir", str(tmp_path / "w"))
    assert "restored checkpoint" in out2


def test_serve_launcher():
    out = _run("repro.launch.serve", "--arch", "smollm-135m", "--reduce",
               "--requests", "2", "--prompt-len", "8", "--new-tokens", "4")
    assert "tok/s" in out


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(monkeypatch):
    import jax

    from repro.common import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert path == compile_cache.enable_compile_cache()  # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    """No accelerator: non-zero exit and no result line — from the repo,
    and from a directory holding the script alone."""
    import shutil
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for where in (REPO, str(alone)):
        out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=where,
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode != 0, out.stdout[-2000:]
        assert '"ok"' not in out.stdout
