"""`chip_smoke.py` has no CPU path: without a TPU it exits non-zero and prints
no result line — from the checkout, and from a directory that holds the
script and nothing else of the repo. Run as a child held to the CPU backend
(the script itself is only ever run whole on the chip)."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
@pytest.mark.parametrize("argv", [(), ("--chips", "4")],
                         ids=["one-chip", "four-chips"])
def test_chip_smoke_refuses_a_backend_that_is_not_tpu(tmp_path, alone, argv):
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        cwd = str(tmp_path)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script), *argv], cwd=cwd,
                          env=env, timeout=300, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not tpu" in proc.stderr
