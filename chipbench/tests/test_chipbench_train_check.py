"""Train cells end to end on the CPU at a tiny size: sound runs are
correct, each fault a train cell can have is caught, and the
lower-precision control fails the comparison."""
import pytest

import chipbench_tiny as T


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return T.make_base(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", T.TRAIN)
def test_sound_run_is_correct(base, workload):
    res = T.run_cell(base, workload, seed=2 ** 33 + 17)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(T.limits_for(workload))
    m = res["metrics"]
    assert m["train_tokens_per_s"]["value"] > 0
    assert set(m) == {"train_tokens_per_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch",
                                   "zero_factors"])
def test_fault_is_caught(base, fault):
    res = T.run_cell(base, T.TRAIN[0], seed=5, fault=fault)
    assert not res["correct"], res["checks"]


def test_zero_factors_fail_the_factored_moment(base):
    """Adapprox's factors lost after every step: the norms of the change
    stay within their limits (the RMS clip sets them), the factored
    second moment does not."""
    res = T.run_cell(base, T.TRAIN[0], seed=6, fault="zero_factors")
    checks = res["checks"]
    assert checks["param_change_gap"]["value"] <= \
        checks["param_change_gap"]["limit"]
    assert checks["factored_v_gap"]["value"] > \
        checks["factored_v_gap"]["limit"]


def test_lower_precision_control_fails():
    import json
    import run
    drv = run.load_module(T.HERE / "drivers" / "train.py")
    import reference
    cfg = {"model": dict(T._MODEL, arch="tiny-train", vocab=1024,
                         max_seq_len=64)}
    traffic = json.loads((T.HERE / "traffic" / "train.adapprox.json")
                         .read_text())
    args = (reference, cfg["model"], traffic["optimizer"], 3, 4, 32,
            traffic["ref_steps"], traffic["ref_rows"])
    ref = drv.reference_readings(*args, precision="f32")
    low = drv.reference_readings(*args, precision="fp8")
    nums = drv.readings(low, ref)
    assert any(nums[k] > T.FACTORED_LIMITS[k] for k in nums), nums
