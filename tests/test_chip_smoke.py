"""chip_smoke.py has no CPU mode: with no TPU it must fail fast, name what it
found, and print no result line (the driver runs it in a CPU sandbox first,
where it MUST fail, then on the chip)."""

import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, str(script)], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=120)
    return proc, time.monotonic() - t0


def test_fails_on_cpu_naming_the_device():
    proc, seconds = _run(REPO, REPO / "chip_smoke.py")
    assert proc.returncode not in (0, 2, 3)  # 2/3 are the chip tool's own
    assert "no TPU" in proc.stderr and "cpu:cpu" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert seconds < 60


def test_fails_alone_in_a_directory(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    script = tmp_path / "chip_smoke.py"
    script.write_bytes((REPO / "chip_smoke.py").read_bytes())
    proc, _ = _run(tmp_path, script)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout
