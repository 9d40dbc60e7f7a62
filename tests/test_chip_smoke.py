"""chip_smoke.py off the chip: it refuses to run without a TPU or outside a
checkout, and its train, serve and multi-chip phases run end to end at a
tiny size on the CPU (Pallas kernels in interpret mode, four forced host
devices for the multi-chip phase).  The kernel phase's parity checks are
the interpret-mode parity tests of test_dispatch, test_serve and
test_serve_fast.  The chip run itself is ``python chip_smoke.py`` on a TPU."""

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import registry
from repro.kernels.dispatch import KernelConfig

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

_spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TINY = registry.get_config(chip_smoke.ARCH).reduced(vocab_size=512, dtype="bfloat16")


def _run(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    env.update(extra_env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=560)


def _ok_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{") and json.loads(lines[-1]).get("ok")


def test_refuses_to_run_without_a_tpu():
    out = _run([str(SCRIPT)], cwd=ROOT)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)
    assert "no TPU" in out.stderr


def test_refuses_to_run_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run([str(lone)], cwd=tmp_path)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)
    assert "src" in out.stderr


def test_train_then_serve_phases_tiny(tmp_path):
    t = dict(chip_smoke.TRAIN)
    res = chip_smoke.train_phase(
        TINY, seq=64, ckpt_dir=str(tmp_path), seed=0,
        impl="pallas", interpret=True, **t,
    )
    assert res["outer_syncs"] == t["steps"] // t["inner_steps"]
    serve_cfg = dataclasses.replace(TINY, kernels=KernelConfig("pallas", True))
    summary = chip_smoke.serve_phase(serve_cfg, ckpt_dir=str(tmp_path), seed=0,
                                     **chip_smoke.SERVE)
    assert summary["parity"] is True


@pytest.mark.multidevice
def test_four_chip_phase_on_host_devices():
    """The shard_map-vs-stacked phase on four forced host devices (jnp
    kernels); in float32 the two runtimes agree to rounding."""
    f = chip_smoke.FOUR
    code = f"""
import dataclasses, sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
from repro.configs import registry
cfg = registry.get_config(chip_smoke.ARCH).reduced(vocab_size=512, dtype="float32")
cfg = dataclasses.replace(cfg, num_layers={f['num_layers']})
chip_smoke.LOSS_RTOL = 1e-5
chip_smoke.four_chip_phase(cfg, seed=0, replicas={f['replicas']},
    per_replica_batch={f['per_replica_batch']}, steps={f['steps']},
    inner_steps={f['inner_steps']}, seq=64, impl="jnp")
print("FOUR OK")
"""
    out = _run(["-c", code], cwd=ROOT, extra_env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(ROOT / "src"),
    })
    assert out.returncode == 0, out.stdout + out.stderr
    assert "FOUR OK" in out.stdout
