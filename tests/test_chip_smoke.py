"""CPU rehearsals of ``chip_smoke.py`` and the compile-cache helper.

The smoke's real run needs the chip; here it runs end to end at toy
width (``--rehearse``) in a child process, so the paths, arguments and
control flow of every phase are exercised by tier-1, and the contract
of its last line and of what it leaves on disk is held.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)  # chip_smoke.py lives there


# what building, testing and running leave in a checkout (.gitignore):
# not part of the tree the smoke is given
_LEFT_BEHIND = (".git", ".jax_cache", "__pycache__", ".bench_cache",
                ".cache", ".scratch", "chiprun_out", "logs",
                ".pytest_cache", ".hypothesis")


def _private_checkout(dest):
    """A copy of the checkout for the smoke's child alone. The other
    workers of a parallel run write into the shared one while the child
    runs (the tokenizer's library beside its source on first use, the
    benchmark tests' ``chiprun_out/`` and ``.bench_cache/``, ``logs/``):
    compared there, a file of theirs reads as one the smoke left."""
    shutil.copytree(
        REPO_ROOT, dest, symlinks=True,
        ignore=lambda d, names: [n for n in names if n in _LEFT_BEHIND
                                 or n.endswith(".so")])
    return str(dest)


def _tree_files(root):
    """Every file under ``root``, outside the compile cache and Python's
    bytecode."""
    out = set()
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in (".jax_cache", "__pycache__")]
        out.update(os.path.join(d, f) for f in files)
    return out


@pytest.fixture
def cache_config():
    """Restore the cache flags the helper may set."""
    names = ("jax_compilation_cache_dir",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def _run_smoke(*argv, env=None, devices=1, root=REPO_ROOT):
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_ENABLE_COMPILATION_CACHE"}
    child_env["JAX_PLATFORMS"] = "cpu"
    child_env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    child_env.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"), *argv],
        env=child_env, capture_output=True, text=True, timeout=600,
        cwd=root)


def test_rehearsal_runs_every_phase_and_leaves_the_tree_alone(tmp_path):
    cache = tmp_path / "cache"
    root = _private_checkout(tmp_path / "checkout")
    before = _tree_files(root)
    assert os.path.join(root, "chip_smoke.py") in before and len(before) > 300
    proc = _run_smoke("--rehearse", root=root,
                      env={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    for phase in ("train", "checkpoint", "serve", "decode"):
        assert any(ln.startswith(f"[smoke] phase {phase}: PASS")
                   for ln in lines), (phase, proc.stdout[-3000:])
    last = json.loads(lines[-1])
    # the platform is not tpu, so ok is never true — phases passing
    # shows in the exit code and the earlier lines
    assert last == {"ok": False,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert '"ok": true' not in proc.stdout
    # the compile cache went where the variable says and nowhere else;
    # checkpoints, logs and exec-cache blobs left with the temp dir
    assert any(cache.iterdir())
    assert _tree_files(root) == before


def test_without_a_tpu_the_plain_command_fails_and_prints_no_result():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_a_failing_phase_is_a_nonzero_exit(monkeypatch, capsys,
                                           cache_config):
    import chip_smoke

    def boom(*args, **kwargs):
        raise RuntimeError("injected")

    monkeypatch.setattr(chip_smoke, "phase_train", boom)
    rc = chip_smoke.main(["--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc != 0
    assert any("phase train: FAIL" in ln for ln in out)
    assert json.loads(out[-1])["ok"] is False


def test_four_device_rehearsal_shards_over_four_devices(tmp_path):
    proc = _run_smoke("--rehearse", "--chips", "4", devices=4,
                      env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert any("shards" in ln and "on devices [0, 1, 2, 3]" in ln
               for ln in lines), proc.stdout[-3000:]
    assert any("first-step loss agrees" in ln for ln in lines)
    for phase in ("train_dp2_tp2", "train_one_chip"):
        assert any(ln.startswith(f"[smoke] phase {phase}: PASS")
                   for ln in lines)
    # only the two train phases ran, and the count is 4
    assert not any("phase serve" in ln or "phase decode" in ln
                   for ln in lines)
    assert json.loads(lines[-1])["device"]["count"] == 4


# --- the compile-cache helper ------------------------------------------------


def test_compile_cache_named_by_the_environment_sets_nothing(
        monkeypatch, tmp_path, cache_config):
    from perceiver_tpu.cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself at import; the code set no directory
    assert jax.config.jax_compilation_cache_dir == before
    # a cached executable must carry this program's names, not an older
    # program's: profiles are read by name stack
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_config):
    from perceiver_tpu.cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_compilation_cache_include_metadata_in_key
