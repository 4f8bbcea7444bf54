"""chip_smoke.py's contract off the chip: without a TPU it exits non-zero
and prints no result, and its phases pass as a CPU rehearsal at a small
size (the same oracles, monitors and comparisons the chip run uses)."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, cwd=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, SMOKE if cwd == REPO else "chip_smoke.py", *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=timeout,
    )


def test_no_tpu_no_result():
    out = _smoke("--rows", "1000", "--profile-rows", "1000")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_alone_in_a_directory_no_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _smoke("--cpu-rehearsal", cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_passes(chips):
    # rows above the compacting key buffer (2^20), so the verify phase's
    # grouping set takes the compacting device frequency table
    out = _smoke("--cpu-rehearsal", "--chips", str(chips),
                 "--rows", "1200000", "--profile-rows", "60000")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "cpu rehearsal passed" in out.stdout
    assert '"ok"' not in out.stdout
    assert "FAILED" not in out.stdout
