"""The four-chip solve loop (``bench/loops/solve_distributed.py``, which no
cell of ``BENCHMARK.json`` runs yet) on four virtual CPU devices: a sound
run is correct, and one with the exchange between chips left out is not.

Virtual devices must be asked for before JAX starts, so the runs happen in
a child process."""

import json
import os
import subprocess
import sys

import benchtest

CHILD = r"""
import json, sys, time
sys.path.insert(0, {tests!r})
import jax
import benchtest
from harness.runner import run_cell

suite = benchtest.tiny_suite({tmp!r})
cell = "tiny_f4.ring4"
out = {{}}
c = suite.cell(cell)
c.chips = 4
out["sound"] = run_cell(c, 2**31 + 5, 0.5, False, time.monotonic(), require_tpu=False)
# the exchange left out: every collective sum returns this chip's own part
jax.lax.psum = lambda x, axis_name, **kw: x
c = suite.cell(cell)
c.chips = 4
out["no_exchange"] = run_cell(c, 2**31 + 5, 0.5, False, time.monotonic(), require_tpu=False)
print(json.dumps(out))
"""


def test_ring_cell_on_four_devices_and_its_missing_exchange(tmp_path):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(benchtest.ROOT, "src"),
    )
    tests = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(tests=tests, tmp=str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sound, broken = out["sound"], out["no_exchange"]
    assert sound["device"]["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    assert broken["correct"] is False, broken["checks"]
