"""Rehearsal of ``chip_smoke.py`` on the CPU: every request and comparison
runs, all results compare equal, and then the script FAILS — its last check
is the platform verdict, and a CPU is not a chip."""

import os
import subprocess
import sys

import pytest

from spark_rapids_jni_tpu.utils.config import child_environ

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_smoke(*args):
    env = child_environ()  # JAX_PLATFORMS=cpu by inheritance (conftest.py)
    # one device, like the chip the driver gives it
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + (
        "4" if "--chips" in args else "1")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("args,evidence", [
    (("--rows", "24000"), "warm run adds no engine.segment.compile"),
    (("--rows", "24000", "--chips", "4"), "every device sent rows"),
], ids=["one-chip", "four-chips"])
def test_rehearsal_passes_every_check_then_fails_on_the_platform(args,
                                                                 evidence):
    r = run_smoke(*args)
    out = r.stdout
    assert r.returncode != 0, out
    assert '"ok": true' not in out
    assert "smoke: all results equal the reference" in out, out + r.stderr
    assert evidence in out
    assert "engine.degraded is 0" in out
    assert "the client process initialised no jax backend" in out
    assert "SMOKE FAILED: server computed on platform 'cpu'" in \
        out.rstrip().splitlines()[-1]
