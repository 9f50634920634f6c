"""Hardened characterization pool: retries, rebuilds, quarantine.

Every parallel scenario arms the fault plan through the ``REPRO_FAULTS``
environment (inherited by the spawn workers — the parent stays disarmed)
and bounds the blast radius with ``REPRO_FAULTS_ONCE_DIR`` so a retried
task landing on a fresh worker cannot re-fire the fault forever.  The
invariant under every scenario is the same: the surviving result is
bit-identical to a clean serial run.
"""

import pytest

from repro.core.circuits import benchmark_suite
from repro.core.transforms import (
    CharacterizationError,
    PoolPolicy,
    characterize_suite,
)
from repro.runtime import faults

CIRCUITS = ["adder", "bar", "max"]
RECIPES = [(), ("Rw",), ("Rf",), ("Ba", "Rw")]
FAST = PoolPolicy(backoff_s=0.01, backoff_cap_s=0.1)


@pytest.fixture(autouse=True)
def _disarmed():
    """The parent process stays disarmed even when REPRO_FAULTS is set
    for the spawn workers (disable() pins the parent's env check)."""
    faults.disable()
    yield
    faults.disable()


@pytest.fixture(scope="module")
def suite_circuits():
    return benchmark_suite("tiny", only=CIRCUITS)


@pytest.fixture(scope="module")
def clean(suite_circuits):
    return characterize_suite(
        suite_circuits, RECIPES, n_jobs=1, backend="python"
    )


def _arm(monkeypatch, tmp_path, spec):
    monkeypatch.setenv("REPRO_FAULTS", spec)
    monkeypatch.setenv("REPRO_FAULTS_SEED", "0")
    once = tmp_path / "once"
    monkeypatch.setenv("REPRO_FAULTS_ONCE_DIR", str(once))
    return once


def test_worker_raise_is_retried_to_parity(
    suite_circuits, clean, monkeypatch, tmp_path
):
    once = _arm(monkeypatch, tmp_path, "pool.task:raise")
    out = characterize_suite(
        suite_circuits, RECIPES, n_jobs=2, backend="python", policy=FAST
    )
    assert out == clean
    assert len(list(once.iterdir())) == 1  # the fault fired exactly once


def test_worker_hard_exit_rebuilds_pool(
    suite_circuits, clean, monkeypatch, tmp_path
):
    """os._exit in a worker breaks the whole ProcessPoolExecutor; the
    scheduler must rebuild it and re-dispatch every in-flight task."""
    _arm(monkeypatch, tmp_path, "pool.task:exit")
    out = characterize_suite(
        suite_circuits, RECIPES, n_jobs=2, backend="python", policy=FAST
    )
    assert out == clean


def test_hung_worker_hits_deadline_and_recovers(
    suite_circuits, clean, monkeypatch, tmp_path
):
    _arm(monkeypatch, tmp_path, "pool.task:hang:::1:30")
    policy = PoolPolicy(
        task_deadline_s=1.0, backoff_s=0.01, backoff_cap_s=0.1
    )
    out = characterize_suite(
        suite_circuits, RECIPES, n_jobs=2, backend="python", policy=policy
    )
    assert out == clean


def test_poisoned_task_quarantines_circuit_only(
    suite_circuits, clean, monkeypatch, tmp_path
):
    # Every 'bar' task raises, forever: retries exhaust, bar is
    # quarantined, and the rest of the suite still matches the clean run.
    _arm(monkeypatch, tmp_path, "pool.task:raise:bar::inf")
    failures = {}
    out = characterize_suite(
        suite_circuits, RECIPES, n_jobs=2, backend="python", policy=FAST,
        failures=failures,
    )
    assert set(failures) == {"bar"}
    err = failures["bar"]
    assert isinstance(err, CharacterizationError) and err.circuit == "bar"
    assert out == {n: clean[n] for n in CIRCUITS if n != "bar"}
    assert list(out) == [n for n in CIRCUITS if n != "bar"]


def test_poisoned_task_raises_without_quarantine_optin(
    suite_circuits, monkeypatch, tmp_path
):
    _arm(monkeypatch, tmp_path, "pool.task:raise:bar::inf")
    with pytest.raises(CharacterizationError, match="bar"):
        characterize_suite(
            suite_circuits, RECIPES, n_jobs=2, backend="python", policy=FAST
        )


def test_front_half_failure_quarantines_serially(suite_circuits, clean):
    """The per-circuit front loop (fingerprint, cache probe, runner
    construction) quarantines too — exercised in process via the
    cha.backend point on the serial path."""
    with faults.injected(
        faults.FaultRule("cha.backend", "raise", match=":bar")
    ):
        failures = {}
        out = characterize_suite(
            suite_circuits, RECIPES, n_jobs=1, backend="python",
            failures=failures,
        )
    assert set(failures) == {"bar"}
    assert out == {n: clean[n] for n in CIRCUITS if n != "bar"}

    with faults.injected(
        faults.FaultRule("cha.backend", "raise", match=":bar")
    ):
        with pytest.raises(CharacterizationError, match="bar"):
            characterize_suite(
                suite_circuits, RECIPES, n_jobs=1, backend="python"
            )


@pytest.mark.parametrize("n_jobs, env_jobs", [(4, None), (None, "4")])
def test_device_backend_never_starts_a_pool(
    suite_circuits, clean, monkeypatch, n_jobs, env_jobs
):
    """Pool workers would each import jax and claim the accelerator the
    parent holds, so the device backend runs serially in-process
    whatever ``n_jobs`` / ``REPRO_CHA_JOBS`` ask for."""
    import concurrent.futures

    def no_pool(*args, **kwargs):
        raise AssertionError("device backend started a process pool")

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", no_pool)
    if env_jobs is not None:
        monkeypatch.setenv("REPRO_CHA_JOBS", env_jobs)
    out = characterize_suite(
        suite_circuits, RECIPES, n_jobs=n_jobs, backend="device"
    )
    assert out == clean
