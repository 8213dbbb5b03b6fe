"""chip_smoke.py must refuse to run without the chip.

The script is the quickest proof that the system starts on the TPU; a run
that quietly carried on on the host would prove nothing.  Here (CPU only)
it has to exit non-zero in its device phase, before any model is built,
and print no result line.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_in_the_device_phase_on_cpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0, r.stdout
    lines = r.stdout.strip().splitlines()
    assert lines[-1] == "[smoke] FAILED phase=device", r.stdout
    assert not any(l.startswith("{") for l in lines), r.stdout
    # it stopped before the train phase could build anything
    assert "phase train" not in r.stdout and "no accelerator" in r.stderr
