"""chip_smoke.py's phases on the CPU at tiny widths, and its refusals.

The script itself runs only on a TPU; here its phase functions run the same
train_loop / serving calls with the same cross-phase checks on a 2-layer
d_model-64 vocab-512 LM (Pallas kernels in interpret mode), and the
four-chip phases run on four forced host devices in a subprocess.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.train import tiny_lm_config  # noqa: E402

TINY = {"d_model": 64, "layers": 2, "vocab": 512}


def test_one_chip_phases_agree_at_tiny_width():
    recs = chip_smoke.run_one_chip(tiny_lm_config(**TINY), seq=16)
    by = {r["phase"]: r for r in recs}
    assert list(by) == ["a", "b", "c", "d"]
    for name in "abc":
        assert len(by[name]["losses"]) == chip_smoke.STEPS
        assert chip_smoke.agree(by[name], by["a"]) <= chip_smoke.RTOL
    # off the chip every kernel runs in interpret mode: no Mosaic call
    assert by["b"]["kernel_programs"] == by["c"]["kernel_programs"] == 0
    tokens = by["d"]["tokens"]
    assert len(tokens) == chip_smoke.N_AGENTS
    assert all(len(t) == chip_smoke.PROMPT + chip_smoke.NEW_TOKENS
               for t in tokens)
    assert tokens == by["d"]["per_agent_tokens"]


def test_agree_fails_beyond_tolerance():
    ref = {"phase": "a", "losses": [10.0, 9.0]}
    ok = {"phase": "b", "losses": [10.0, 9.0 * (1 + 0.5e-3)]}
    bad = {"phase": "c", "losses": [10.0, 9.0 * (1 + 2e-3)]}
    assert chip_smoke.agree(ok, ref) <= chip_smoke.RTOL
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.agree(bad, ref)


def test_check_spread_needs_every_device():
    rec = {"phase": "s1d", "state_bytes": 400,
           "state_bytes_per_device": [100, 100, 100, 100],
           "peak_bytes_in_use": [0, 0, 0, 0]}
    chip_smoke.check_spread(rec)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_spread(dict(rec, state_bytes_per_device=[400, 0,
                                                                  0, 0]))
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_spread(dict(rec, peak_bytes_in_use=[900, 0, 0, 0]))


_FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.launch.train import tiny_lm_config
recs = chip_smoke.run_four_chips(
    tiny_lm_config(d_model=64, layers=2, vocab=512), seq=16)
assert [r["phase"] for r in recs] == ["ref", "s1d", "s1s", "s2d"]
assert recs[0]["state_bytes_per_device"][1:] == [0, 0, 0]
print("FOUR_OK")
"""


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_four_chip_phases_agree_on_forced_host_devices():
    res = subprocess.run([sys.executable, "-c", _FOUR, ROOT], env=_env(),
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "FOUR_OK" in res.stdout


def test_entry_point_refuses_cpu(capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "not 'tpu'" in err


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and the code then sets nothing;
    without it the cache goes to the fixed .jax_cache/ at the repo root."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _no_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return not json.loads(lines[-1]).get("ok")
    except (ValueError, AttributeError):
        return True


def test_script_exits_nonzero_without_a_chip():
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert _no_result(res.stdout)


def test_script_imports_no_simulated_pod_tooling():
    """dryrun.py sets XLA_FLAGS as it is imported."""
    code = ("import sys, chip_smoke; bad = [m for m in ('repro.launch."
            "dryrun',) if m in sys.modules]; assert not bad, bad")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = _env()
    env.pop("PYTHONPATH")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert _no_result(res.stdout)
