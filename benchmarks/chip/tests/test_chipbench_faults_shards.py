"""Runs of the vgg16-cifar10.shards4-t25 cell, cut to a CPU's size, on
four virtual CPU devices in a child process (the device count is fixed
when JAX starts), with the chip check skipped: ``correct`` holds for the
program as it is, and comes out false with a round that returns its
state unchanged, half of every batch left out, or the exchange between
chips left out underneath the harness."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
RUN = """
import json, sys
sys.path[:0] = [{tests!r}]
import pytest
import chipbench_tiny as tiny
import repro.launch.env as env
from benchmarks.chip import harness
mp = pytest.MonkeyPatch()
mp.setattr(env, "enable_compile_cache", lambda: "off")
tiny.plant({fault!r}, mp)
cell = tiny.tiny_cell("vgg16-cifar10.shards4-t25")
res = harness.run(cell, seed=2 ** 31 + 11, seconds=0.3, traced=False,
                  t_start=0.0, require_chip=False)
print(json.dumps({{"correct": res["correct"], "failed": res["failed"],
                  "checks": res["checks"]}}))
"""


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "no_exchange"])
def test_correct_only_for_the_sound_program(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", RUN.format(tests=str(TESTS), fault=fault)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["checks"]
    assert res["failed"] == 0
