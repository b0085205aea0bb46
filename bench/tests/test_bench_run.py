"""The harness end to end on the CPU at a tiny size (the look for a chip
skipped): a sound run is correct, a run with the timed path broken
underneath is not, and the bfloat16 control fails a limit.  Without a TPU,
or without the program beside it, the command exits non-zero and prints no
result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 977


def _tiny(cell, per_worker=64):
    from bench import run

    wl, cfg = run.load_cell(cell)
    cfg = dict(cfg, num_workers=4, train_per_worker=per_worker, test_size=300, rounds=6)
    if cfg["model"]["kind"] == "vgg":
        cfg["model"] = dict(cfg["model"], plan=[8, 8, "M", 16, "M", 16, "M"], image_size=8)
    else:
        cfg["model"] = dict(cfg["model"], stem=8, stages=[[2, 8], [1, 16]], image_size=8)
    return wl, cfg


def _measure(cell, per_worker=64, **kw):
    """A run at 4 workers of ``per_worker`` images, 6 rounds, 8 px."""
    from bench import run

    wl, cfg = _tiny(cell, per_worker)
    return run.measure(wl, cfg, SEED, 0.1, False, require_chip=False, **kw)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16-w10-sync", "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj)


def test_exits_nonzero_without_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    _no_result(p.stdout)


@pytest.mark.parametrize("cell", ["vgg16-w10-sync", "resnet20-w10-sync"])
def test_sound_run_is_correct(cell):
    out = _measure(cell)
    assert out["correct"], out
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert list(out)[-1] == "compared"


def test_state_left_unchanged_is_not_correct(monkeypatch):
    import repro.core.worker as worker

    monkeypatch.setattr(worker, "apply_updates", lambda params, updates: params)
    out = _measure("vgg16-w10-sync")
    assert not out["correct"]
    assert out["compared"]["median_change_gap_r1"]["value"] > 0.5


def test_half_batch_left_out_is_not_correct(monkeypatch):
    from repro.core.worker import LocalTrainer

    ce = LocalTrainer._masked_ce

    def half(self, qm, mask, xb, yb):
        n = xb.shape[0] // 2
        return ce(self, qm, mask, xb[:n], yb[:n])

    monkeypatch.setattr(LocalTrainer, "_masked_ce", half)
    out = _measure("vgg16-w10-sync", per_worker=256)
    assert not out["correct"]


def test_bf16_control_fails_a_limit():
    from bench import readings, run

    wl, cfg = _tiny("vgg16-w10-sync", 1024)
    run.start_jax(1, require_chip=False)
    got = readings.read_seed(wl, cfg, SEED, controls=["bf16"])
    limits = {k: lim for k, lim in wl["limits"].items() if k != "repeat_gap"}
    assert not any(got["program"][k] > lim for k, lim in limits.items()), got["program"]
    assert any(got["bf16"][k] > lim for k, lim in limits.items()), got["bf16"]
