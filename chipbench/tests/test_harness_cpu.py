"""The harness end to end on the host CPU at tiny sizes, the look for a
chip skipped: a sound run is correct, and every planted fault and the
control (parameters stored in bfloat16) make ``correct`` false."""
import time

import pytest

from chipbench import faults, harness
from chipbench.registry import resolve
from chipbench.tests.tiny import TINY, make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _run(root, family, seed=2**31 + 3, **kw):
    cell = resolve(root, f"{family}.tiny")
    return harness.run_cell(root, cell, seed, kw.pop("seconds", 0.2),
                            kw.pop("trace", False),
                            t_start=time.perf_counter(), require_tpu=False,
                            use_cache=False, log=lambda rec: None, **kw)


@pytest.mark.parametrize("family", sorted(TINY))
def test_sound_run_is_correct(root, family):
    r = _run(root, family, trace=family == "tiny-dense", seconds=0.5)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"loss_rel", "update_gap", "sync_spread"}
    assert r["device"]["platform"] == "cpu"
    if family == "tiny-dense":   # host-only trace: no device metric
        assert r["metrics"] == {}
    else:
        assert set(r["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
        assert r["metrics"]["tokens_per_s_per_chip"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("family", sorted(TINY))
def test_planted_fault_is_not_correct(root, family, fault):
    with faults.FAULTS[fault]():
        r = _run(root, family)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("family", sorted(TINY))
def test_control_in_lower_precision_is_not_correct(root, family):
    r = _run(root, family, model_overrides={"param_dtype": "bfloat16"})
    assert not r["correct"], r["checks"]
    assert r["checks"]["update_gap"]["value"] > 0.1


def test_reference_follows_only_the_check_steps(tmp_path):
    # an average within the round ends the check; the rest of the round
    # runs before the window without a reference
    root = make_root(tmp_path, traffic={"check_steps": 2})
    cell = resolve(root, "tiny-ssd.tiny")
    prep = harness.prepare(cell)
    _, _, losses, readings = harness.first_round(prep, 5)
    assert len(losses) == 2 and sorted(readings) == [2]
    r = _run(root, "tiny-ssd")
    assert r["correct"], r["checks"]
    with pytest.raises(ValueError):
        harness.prepare(resolve(make_root(tmp_path / "odd",
                                          traffic={"check_steps": 3}),
                                "tiny-ssd.tiny"))
