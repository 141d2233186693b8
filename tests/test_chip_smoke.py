"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to
report a result without a TPU."""

import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _smoke_config():
    from repro.configs import get_smoke_config
    return get_smoke_config("phi3-mini-3.8b")


def test_one_chip_phase_passes_at_smoke_size(tmp_path):
    """Cold start, two periodic saves, eviction, urgent save, streaming
    resume; the resumed loss equals the uninterrupted one bit for bit."""
    failed = _chip_smoke().one_chip(_smoke_config(), batch=2, seq_len=64,
                                    ckpt_root=str(tmp_path))
    assert failed == []


FOUR_DEVICES = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke
from repro.configs import get_smoke_config
failed = chip_smoke.four_chips(get_smoke_config("phi3-mini-3.8b"), batch=2,
                               seq_len=64, ckpt_root={ckpt!r})
sys.exit(1 if failed else 0)
"""


def test_four_chip_phase_passes_on_four_host_devices(tmp_path):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = FOUR_DEVICES.format(root=ROOT, ckpt=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "check FAIL" not in r.stdout


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_a_tpu(tmp_path, alone):
    """No TPU (here: the CPU backend), or no repository around the script:
    a non-zero exit and no result line."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        with open(SCRIPT) as src, open(script, "w") as dst:
            dst.write(src.read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
