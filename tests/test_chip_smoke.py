"""chip_smoke.py off the chip: it refuses the CPU, and its phases pass at
smoke size (one CPU device in-process, four virtual devices in a child)."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SMOKE)], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_outside_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(SMOKE.read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(alone)], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_one_chip_phases_smoke_size(chip_smoke):
    from repro.models import get_smoke_config
    cfg = get_smoke_config(chip_smoke.ARCH)
    assert chip_smoke.train_phase(cfg, seq_len=16, batch=4) > 0
    assert chip_smoke.serve_phase(cfg, prompt_len=8, max_new=8,
                                  fail_at_step=3) > 0


def test_check_raises(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


@pytest.mark.slow
def test_four_chip_phases_on_virtual_devices(multidevice):
    out = multidevice(f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SMOKE)!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.models import get_smoke_config
mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
cs.all_reduce_phase(mesh, per_chip=4099)
cs.train_parity_phase(get_smoke_config(cs.ARCH), seq_len=16)
print("FOUR_CHIP_PHASES_OK")
""", devices=4)
    assert "FOUR_CHIP_PHASES_OK" in out
    for mode in ("xla", "ring", "tree", "r2ccl", "recursive"):
        assert f"all_reduce {mode}:" in out
    assert "train parity r2ccl degraded vs xla" in out
