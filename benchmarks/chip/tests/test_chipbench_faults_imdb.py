"""Runs of the imdb-cnnlstm.cohort256-t50-qint8 cell, cut to a CPU's size,
with the chip check skipped: ``correct`` holds for the program as it is,
and comes out false with the timed path broken underneath (a round that
returns its state unchanged, half of every batch left out, one client's
update altered where it is produced, the loader feeding another
client's rows or labels out of line) and with the float32 reference's
bfloat16 control in the program's place."""
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from benchmarks.chip import compare, harness

WORKLOAD = "imdb-cnnlstm.cohort256-t50-qint8"


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.env as env
    monkeypatch.setattr(env, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch", "half_cohort",
                                   "flip_client", "other_client",
                                   "labels_shifted"])
def test_correct_only_for_the_sound_program(fault, monkeypatch):
    cell = tiny.tiny_cell(WORKLOAD)
    tiny.plant(fault, monkeypatch,
               chunk=cell.traffic["fl"]["cohort_chunk"])
    res = harness.run(cell, seed=2 ** 31 + 9, seconds=0.3, traced=False,
                      t_start=0.0, require_chip=False)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(m["value"] > 0 for m in res["metrics"].values()
               if m["unit"] != "MB")


def test_bfloat16_control_fails_the_limits():
    cell = tiny.tiny_cell(WORKLOAD)
    st = harness.build(cell, 7)
    obs, feed, n_tr = st.observed, st.feed, st.n_train
    del st
    ref = harness.reference_readings(cell, 7, feed)
    assert compare.passed(compare.checks(obs, ref, feed, n_tr, cell.limits))
    ctl = harness.reference_readings(cell, 7, feed, dtype=jnp.bfloat16,
                                   precision=None)
    checks = compare.checks(ctl, ref, feed, n_tr, cell.limits)
    assert not compare.passed(checks), checks
