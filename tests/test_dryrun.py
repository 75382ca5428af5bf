"""Guard for the driver's judged multichip artifact (VERDICT r3 next
#1d): run ``__graft_entry__.dryrun_multichip(8)`` the way the driver
does, so it can never silently rot again.

Runs in a SUBPROCESS: the dryrun pins jax_platforms=cpu and sets its
own virtual-device count, which must happen before the first backend
use and so cannot share this pytest process.  JAX_PLATFORMS is
deliberately NOT exported — the dryrun must pin the CPU platform on its
own, whatever accelerator library the box has installed.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.smoke
def test_dryrun_multichip_8():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    env.pop("XLA_FLAGS", None)  # __graft_entry__ sets the device count
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "8b=compiled ok" in r.stdout
    # every leg actually ran (pp/sp/ep/continuous-engine at n=8)
    assert "sp=2 pp=2 ep=2 ce=2" in r.stdout
