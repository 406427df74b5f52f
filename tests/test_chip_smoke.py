"""``chip_smoke.py`` must fail off the chip: no JSON result, non-zero
exit. A smoke that could pass on the CPU would prove nothing."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """Run on the CPU from the checkout, and from a directory holding
    the script and nothing else of the repository."""
    script = SMOKE
    if where == "alone":
        script = tmp_path / SMOKE.name
        shutil.copy(SMOKE, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
