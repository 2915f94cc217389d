"""chip_smoke.py on the CPU: it must refuse to report without a TPU, and
each of its phases must pass on the ``.reduced()`` configs (the chip runs
them at published widths)."""
import importlib
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _smoke():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    return importlib.import_module("chip_smoke")


def _env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"), **extra)


def test_exits_nonzero_without_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_env(), cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "need 1 TPU" in r.stderr


def test_syscall_phase(tmp_path):
    _smoke().phase_syscalls(0, tmp_path)


def test_rwkv6_server_phase_reduced():
    from repro.configs import get_config
    _smoke().phase_rwkv(get_config("rwkv6-3b").reduced(), 0)


def test_continuous_engine_phase_reduced():
    from repro.configs import get_config
    _smoke().phase_continuous(get_config("internlm2-20b").reduced(), 0)


def test_four_chip_training_phase_reduced(tmp_path):
    """The --four-chips comparison on four virtual CPU devices, in a
    subprocess so the device-count flag never leaks into this one."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "from pathlib import Path\n"
        "import chip_smoke\n"
        "from repro.configs import get_config\n"
        "chip_smoke.phase_four_chips(get_config('rwkv6-3b').reduced(), 0,\n"
        f"                           Path({str(tmp_path)!r}))\n"
        "print('FOUR_CHIPS_OK')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert "FOUR_CHIPS_OK" in r.stdout, r.stdout + r.stderr
    assert "4 chip(s): losses" in r.stdout
