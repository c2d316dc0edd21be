"""Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmarks/test_correct.py -q`
(~3 min). What decides `correct`, at a size a test run can hold:

- the program as configured comes out correct on three seeds, and the
  control (the reference in the program's place, single bf16) does not;
- it comes out correct on a data set that no limit was set from (a
  benchmark run's seed only reorders the columns of one data set);
- the program run with coarser bins than the configuration states (the
  control of the bin table's numbers) does not;
- with the timed path broken underneath — the step returns its state
  unchanged; every second row left out of the batch; an answer altered
  where it is produced — the rest of a run sees `correct` come out false.

The harness's look for a chip is skipped: these call the mode's `run`
the way `run.py` does after it. (The exchange between chips does not
exist in a one-chip cell.)
"""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import run as harness  # noqa: E402

CELLS = ("higgs-train-1chip",)
ROWS = {"higgs-train-1chip": 60000}


def drive(workload, seed, **extra):
    loaded = harness.load_cell(workload)
    traffic = loaded["traffic"]
    return harness.load_mode(traffic).run(dict({
        "cell": loaded["cell"], "config": loaded["config"],
        "traffic": traffic, "seed": seed, "seconds": 0.5, "trace": False,
        "rows": ROWS[workload], "t_start": time.perf_counter(),
        "limits": loaded["cell"]["limits"], "rehearsal": True}, **extra))


@pytest.mark.parametrize("seed", [3000000019, 7, 123456789])
def test_program_is_correct_and_control_is_not(seed):
    out = drive("higgs-train-1chip", seed, control=True)
    assert out["correct"], out["compared"]
    assert not out["control_correct"], out["control_compared"]


@pytest.mark.parametrize("data_seed", [20260930])
def test_fresh_data_is_correct(data_seed):
    out = drive("higgs-train-1chip", 5, data_seed=data_seed)
    assert out["correct"], out["compared"]


def test_coarse_bins_are_not_correct():
    out = drive("higgs-train-1chip", 11, params_override={"max_bin": 31})
    assert not out["correct"], out["compared"]
    assert out["compared"]["bin_count_mismatch"]["value"] == 28
    assert out["compared"]["bin_pop_gap"]["value"] > \
        out["compared"]["bin_pop_gap"]["limit"]


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(workload, fault):
    out = drive(workload, 11, fault=fault)
    assert not out["correct"], out["compared"]
