"""chip_smoke.py on the CPU, and the compile-cache placement rule.

The chip check itself runs `python chip_smoke.py` on a TPU; tier-1 keeps
its phases alive at a few thousand rows on the 8-virtual-device CPU
backend (which also covers the data-parallel phase), pins that the
default invocation refuses a CPU backend before training, and pins the
one cache rule of lightgbm_tpu/__init__.py in fresh processes (import
time matters).
"""
import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _child_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("LIGHTGBM_TPU_COMPILE_CACHE", None)
    env.update(extra)
    return env


def test_phases_pass_in_process_on_cpu(capsys):
    import chip_smoke
    from lightgbm_tpu import telemetry
    try:
        rc = chip_smoke.main(["--rows", "6000", "--holdout", "1500",
                              "--expect-platform", "cpu"])
    finally:
        telemetry.observer().uninstall()
        telemetry.observer().reset()
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    # the last line is the verdict with exactly these keys; the line
    # before it carries the details
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    prefix = "[chip_smoke] summary: "
    assert lines[-2].startswith(prefix)
    summary = json.loads(lines[-2][len(prefix):])
    assert rc == 0 and summary["ok"] is True
    assert summary["device"] == json.loads(lines[-1])["device"]
    assert summary["max_bin"] == 63 and summary["num_leaves"] == 255
    assert summary["schedule"]["subtract"] is True
    assert len(summary["tree_leaves"]) == chip_smoke.ITERATIONS
    assert summary["repeat_train_compiles"] == 0
    # 8 host devices >= 4: the data-parallel phase ran and spread the rows
    dp = summary["data_parallel"]
    assert dp["devices"] == 8 and dp["tree_structure_equal_to_serial"]
    # under the data axis too: the cache, 24 smaller children a pass
    assert dp["subtract"] is True and dp["batch_k"] == 24
    assert "subtract=True batch_k=24" in out
    for phase in ("train", "predict", "serve", "repeat_train",
                  "data_parallel"):
        assert f"[chip_smoke] {phase}: ok" in out


def test_default_invocation_refuses_cpu_before_training():
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=_child_env(), capture_output=True, text=True,
                         timeout=300, cwd=REPO)
    assert res.returncode != 0
    assert "platform=cpu" in res.stdout
    assert "expected 'tpu'" in res.stderr
    assert "train" not in res.stdout
    assert not any(ln.startswith("{") for ln in res.stdout.splitlines())


_CACHE_PROBE = """
import jax
import lightgbm_tpu
from lightgbm_tpu.serving.forest import enable_compile_cache
enable_compile_cache({param!r})
print(jax.config.jax_compilation_cache_dir)
"""


def _cache_dir_in_child(param, **env):
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(param=param)],
        env=_child_env(PYTHONPATH=REPO, **env), capture_output=True,
        text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout.strip().splitlines()[-1]


def test_environment_places_the_cache_whatever_the_param_says(tmp_path):
    placed, param = str(tmp_path / "x"), str(tmp_path / "y")
    assert _cache_dir_in_child(
        param, JAX_COMPILATION_CACHE_DIR=placed) == placed
    assert not os.path.exists(param)


def test_default_cache_is_inside_the_checkout(tmp_path):
    res = subprocess.run(
        [sys.executable, "-c",
         "import jax, lightgbm_tpu; "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=_child_env(PYTHONPATH=REPO), capture_output=True, text=True,
        timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.strip() == os.path.join(REPO, ".jax_cache")
    # without the variable, the parameter may re-point it
    param = str(tmp_path / "y")
    assert _cache_dir_in_child(param) == param
