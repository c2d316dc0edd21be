"""Test configuration: force CPU with 8 virtual devices so distributed
(mesh) paths are exercised without TPU hardware, as SURVEY.md §4 prescribes
(the in-process N-rank fake backend the reference never built). Tests
check behaviour; nothing here is a device measurement.
"""
import os

import pytest

# LGBM_TPU_TEST_PLATFORM=tpu keeps the real accelerator (used by the
# opt-in LGBM_TPU_SLOW_TESTS accuracy-floor runs, which would take hours
# on the CPU backend); everything else runs on the virtual CPU mesh.
if os.environ.get("LGBM_TPU_TEST_PLATFORM", "cpu") == "cpu":
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8").strip()
    # honoured by jax (and inherited by every child a test starts)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    assert jax.devices()[0].platform == "cpu", \
        "tests must run on the CPU backend"
    assert len(jax.devices()) == 8, "tests expect 8 virtual CPU devices"


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Give each test module's compiled programs back when it ends.

    Every XLA:CPU executable holds ~15 memory mappings, and a tier-1
    session compiles thousands of programs in one process: left alone
    the process reaches vm.max_map_count (65,530) about halfway through
    and segfaults inside jaxlib (measured in PR 22: the mapping count
    climbs steadily to 65,076, then the next compile crashes). Dropping
    jax's in-process caches releases the mappings; programs that take
    over a second to compile come back from the persistent cache."""
    yield
    import gc

    import jax
    jax.clear_caches()
    gc.collect()


def pytest_configure(config):
    # tier-1 CI deselects these (`-m 'not slow'`): long benchmark-grade
    # runs (bulk predict throughput, 500-tree latency economics)
    config.addinivalue_line(
        "markers", "slow: long benchmark-grade runs excluded from tier-1")


def pytest_sessionstart(session):
    """Stdout hygiene gate, fail-fast at session start: the ad-hoc AST
    walk that used to live here is now graftlint's `stdout-print` rule
    (lightgbm_tpu/analysis/rules/stdout_print.py — same cli.py/
    __main__.py allowlist, same sys.stderr exemption, plus pragma/
    baseline suppression with mandatory reasons). The FULL rule set runs
    as the tier-1 test tests/test_static_analysis.py; this hook keeps
    only the cheap stdout check so a contract break aborts the session
    before any training-heavy test burns the CI budget."""
    import pathlib

    import pytest

    from lightgbm_tpu.analysis import run
    from lightgbm_tpu.analysis.rules.stdout_print import StdoutPrintRule

    repo = pathlib.Path(__file__).resolve().parent.parent
    # same baseline as the tier-1 gate: a grandfathered (reasoned)
    # finding must not make the whole suite unrunnable at sessionstart
    report = run([str(repo / "lightgbm_tpu")], rules=[StdoutPrintRule()],
                 baseline_path=str(repo / "graftlint_baseline.json"))
    if report.findings:
        raise pytest.UsageError(
            "graftlint stdout-print gate: "
            + "; ".join(f.render() for f in report.findings))


def pytest_collection_modifyitems(config, items):
    """Run the robustness suites (checkpoint/resume, fault injection,
    kill-and-resume cycles) LAST: tier-1 CI runs under a fixed
    wall-clock budget, and the broad regression coverage must not be
    displaced past the cutoff by training-heavy robustness cycles."""
    late_modules = {"tests.test_checkpoint", "tests.test_faults",
                    "test_checkpoint", "test_faults",
                    # new serving coverage rides after the pre-existing
                    # broad regression suites: if the budget cuts
                    # anything, it cuts the newest tests first
                    "tests.test_serving", "test_serving"}
    late_tests = {
        "test_cli_checkpoint_kill_and_resume",
        "test_continued_training_binned_replay_exact",
        "test_continue_from_restores_best_iteration",
        "test_dart_state_roundtrips_through_model_string",
        "test_goss_state_roundtrips_through_model_string",
        "test_nonfinite_gradient_guard_names_objective_and_iteration",
        "test_nonfinite_metric_guard",
    }
    items.sort(key=lambda it: it.module.__name__ in late_modules
               or it.name in late_tests)  # stable sort
