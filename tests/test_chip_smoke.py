"""``chip_smoke.py``'s phases at tiny sizes on the CPU.

The script itself refuses to run anywhere but a TPU; its phases are
plain functions that take their sizes, so the paper round (phase b) runs
here in-process and the four-device scaleout round (phase d) runs in a
subprocess on four virtual CPU devices.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

TINY = dict(n_train=800, n_test=200, n_features=64, rounds=3, n_clients=12,
            m=4, strategy_kwargs={"J": 3}, hidden=(16,), eval_samples=16,
            target_hd=0.8)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paper_round_backends_agree():
    """Phase b: compiled, host and fused pick the same clients every
    round and end at allclose params."""
    _load().paper_round(**TINY)


def test_four_chip_round_on_virtual_devices():
    """Phase d: scaleout over a pod=4 mesh of four distinct devices
    matches compiled on one."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', {str(SCRIPT)!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        f"cs.four_chip_round(**{TINY!r})\n"
        "print('FOUR_CHIP_OK')\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=dict(os.environ))
    assert "FOUR_CHIP_OK" in r.stdout, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "on 4 devices" in r.stdout


def test_refuses_to_run_without_a_tpu():
    """Off the chip the script exits non-zero and never prints the
    ``ok`` line: there is no CPU fallback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU found" in r.stderr
