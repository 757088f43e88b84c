"""Smoke-run every example script at tiny scale in a subprocess.

The examples are the runnable equivalents of the reference's tutorial
notebooks (`/root/reference/README.md:101-103`) and import the installed
package (no sys.path prologue); these tests pin that
they keep running from an arbitrary cwd and produce their output files.
"""

import os
import subprocess
import sys

import pytest

from p2p_tpu.utils.cache import default_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    ("prompt_to_prompt_stable.py", ["--preset", "tiny"], "replace.png"),
    ("equalizer_sweep.py", ["--preset", "tiny"], None),
    ("prompt_to_prompt_ldm.py", ["--preset", "tiny-ldm"], None),
    ("null_text_w_ptp.py", ["--preset", "tiny"], None),
    ("ring_attention_highres.py", ["--preset", "tiny"], "y_hat.png"),
]


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # 8 virtual devices so the sharded examples (equalizer sweep, ring
    # attention) exercise their multi-device paths, matching the suite.
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # The examples import the installed package (`pip install -e .
    # --no-build-isolation --no-deps`); PYTHONPATH keeps this test green on
    # a fresh container where site-packages was reset.
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # Share the suite's persistent compile cache so re-runs are warm.
    # One resolver for the whole repo (p2p_tpu.utils.cache): a pre-set
    # JAX_COMPILATION_CACHE_DIR is respected (shared CI cache), else the
    # repo-local default the in-process conftest also uses.
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   default_cache_dir())
    return env


@pytest.mark.slow
@pytest.mark.parametrize("script,args,want_file",
                         CASES, ids=[c[0] for c in CASES])
def test_example_runs(script, args, want_file, tmp_path):
    out_dir = str(tmp_path / "out")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script),
         *args, "--out-dir", out_dir],
        env=_cpu_env(), cwd=str(tmp_path),  # arbitrary cwd, not the repo
        timeout=900, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    assert proc.returncode == 0, f"{script} failed:\n{proc.stdout[-3000:]}"
    produced = []
    for root, _, files in os.walk(out_dir):
        produced += [os.path.join(root, f) for f in files]
    assert produced, f"{script} wrote nothing under {out_dir}"
    if want_file:
        names = {os.path.basename(p) for p in produced}
        assert want_file in names, f"{script}: {want_file} not in {names}"
