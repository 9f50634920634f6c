"""Journaled resumable sweeps: bit-identical shard/resume parity.

The pinned contract (ISSUE 10 acceptance): a sweep killed mid-run —
whether by an injected crash or a real SIGKILL on a subprocess — resumes
from its journal and assembles a `SelectionResult` equal field-for-field
to an uninterrupted `evaluate_select_suite` over the same suite.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.batch import (  # noqa: E402
    SuiteTable,
    TopologyTable,
    evaluate_select_suite,
)
from repro.core.circuits import benchmark_suite  # noqa: E402
from repro.core.explorer import _opt_and_feasible, _restrict_cha  # noqa: E402
from repro.core.sram import TOPOLOGY_LIBRARY  # noqa: E402
from repro.ckpt.manager import CheckpointManager  # noqa: E402
from repro.core.sweep_runner import run_sweep, sweep_config_key  # noqa: E402
from repro.core.transforms import characterize_suite  # noqa: E402
from repro.runtime import faults  # noqa: E402

CIRCUITS = ["adder", "bar", "max", "sqrt"]
RECIPES = [(), ("Rw",), ("Ba", "Rw"), ("Rf",)]
TOPOS = list(TOPOLOGY_LIBRARY[:5])


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disable()
    yield
    faults.disable()


@pytest.fixture(scope="module")
def suite_circuits():
    return benchmark_suite("tiny", only=CIRCUITS)


@pytest.fixture(scope="module")
def cha_cache(tmp_path_factory, suite_circuits):
    """Warm on-disk characterization cache shared by every run in this
    module, so repeated SweepRunner.run calls skip the front half."""
    root = tmp_path_factory.mktemp("cha")
    characterize_suite(suite_circuits, RECIPES, cache=root, n_jobs=1)
    return root


@pytest.fixture(scope="module")
def direct(suite_circuits, cha_cache):
    """The uninterrupted reference: one unsharded fused suite call."""
    cha = characterize_suite(suite_circuits, RECIPES, cache=cha_cache, n_jobs=1)
    cha = {n: _restrict_cha(cha[n], RECIPES) for n in cha}
    feas = np.zeros((len(cha), len(TOPOS)), dtype=bool)
    for i, n in enumerate(cha):
        _, _, f = _opt_and_feasible(cha[n], TOPOS)
        feas[i] = [t in f for t in TOPOS]
    _, sel = evaluate_select_suite(
        SuiteTable.from_cha(cha), TopologyTable.from_topologies(TOPOS),
        None, feasible=feas,
    )
    return sel


def assert_selection_equal(sel, ref, circuits=None, ref_names=None):
    """Field-for-field bit-identity (optionally on a circuit subset)."""
    rows = (
        slice(None)
        if circuits is None
        else [ref_names.index(c) for c in circuits]
    )
    assert sel.winner_idx.dtype == ref.winner_idx.dtype
    assert np.array_equal(sel.winner_idx, ref.winner_idx[rows])
    assert np.array_equal(sel.nominal_latency_ns, ref.nominal_latency_ns[rows])
    assert np.array_equal(sel.nominal_fits, ref.nominal_fits[rows])
    for k, v in ref.winner_metrics.items():
        assert np.array_equal(sel.winner_metrics[k], v[rows]), k
    if circuits is None:
        assert sel.payload_bytes == ref.payload_bytes


@pytest.mark.parametrize("shard_size", [1, 2, 3, None])
def test_sharded_parity_without_journal(
    suite_circuits, cha_cache, direct, shard_size
):
    out = run_sweep(
        suite_circuits, journal_dir=None, shard_size=shard_size,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out.circuits == tuple(CIRCUITS)
    assert out.shards_resumed == 0 and out.journal_dir is None
    assert_selection_equal(out.selection, direct)


def test_injected_crash_then_resume_bit_identical(
    tmp_path, suite_circuits, cha_cache, direct
):
    journal = tmp_path / "j"
    # Crash (hard FaultError) before the second shard evaluates.
    with faults.injected(
        faults.FaultRule("sweep.shard", "raise", after=1)
    ):
        with pytest.raises(faults.FaultError):
            run_sweep(
                suite_circuits, journal_dir=journal, shard_size=2,
                sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
            )
    # Exactly one shard published before the crash.
    assert len(CheckpointManager(str(journal)).steps()) == 1
    out = run_sweep(
        suite_circuits, journal_dir=journal, shard_size=2,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out.shards_resumed == 1 and out.shards_run == 1
    assert_selection_equal(out.selection, direct)


def test_resume_with_different_shard_size(
    tmp_path, suite_circuits, cha_cache, direct
):
    """Resume is keyed per circuit, so re-chunking the remainder with a
    different shard size still assembles the identical result."""
    journal = tmp_path / "j"
    with faults.injected(
        faults.FaultRule("sweep.shard", "raise", after=1)
    ):
        with pytest.raises(faults.FaultError):
            run_sweep(
                suite_circuits, journal_dir=journal, shard_size=1,
                sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
            )
    out = run_sweep(
        suite_circuits, journal_dir=journal, shard_size=3,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out.shards_resumed == 1
    assert_selection_equal(out.selection, direct)


def test_corrupt_journal_entry_is_evicted_and_redone(
    tmp_path, suite_circuits, cha_cache, direct
):
    journal = tmp_path / "j"
    out = run_sweep(
        suite_circuits, journal_dir=journal, shard_size=2,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out.shards_run == 2
    # The success path does not drain the async writer; do so before
    # poking at the journal files directly.
    CheckpointManager(str(journal)).wait()
    # Tear the tail record of the append-only log behind the manager's
    # back — the frame crc must reject it and only that shard is redone.
    wal = journal / "journal.wal"
    wal.write_bytes(wal.read_bytes()[:-5])
    out2 = run_sweep(
        suite_circuits, journal_dir=journal, shard_size=2,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out2.shards_resumed == 1 and out2.shards_run == 1
    assert_selection_equal(out2.selection, direct)


def test_torn_write_via_journal_fault_recovers(
    tmp_path, suite_circuits, cha_cache, direct
):
    """A corrupt rule at journal.write models a torn log append that
    survives the flush; the reader must skip the damaged frame (re-sync
    on the next frame magic, keeping later records) and redo only that
    shard."""
    journal = tmp_path / "j"
    with faults.injected(
        faults.FaultRule("journal.write", "corrupt")
    ):
        run_sweep(
            suite_circuits, journal_dir=journal, shard_size=2,
            sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
        )
        # drain the async writer while the rule is armed, so the append
        # it tears is not left to a publish after the scope closes
        CheckpointManager(str(journal)).wait()
    out = run_sweep(
        suite_circuits, journal_dir=journal, shard_size=2,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out.shards_run == 1  # the torn shard was redone
    assert_selection_equal(out.selection, direct)


def test_mismatched_config_entries_are_ignored(
    tmp_path, suite_circuits, cha_cache, direct
):
    journal = tmp_path / "j"
    other = [(), ("Rw",)]
    run_sweep(
        suite_circuits, journal_dir=journal, shard_size=2,
        sram_list=TOPOS, recipes=other, cache=cha_cache, n_jobs=1,
    )
    out = run_sweep(
        suite_circuits, journal_dir=journal, shard_size=2,
        sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
    )
    assert out.shards_resumed == 0 and out.shards_run == 2
    assert_selection_equal(out.selection, direct)
    assert sweep_config_key(
        suite_circuits, RECIPES, TOPOS, None, "physical", "list", None
    ) != sweep_config_key(
        suite_circuits, other, TOPOS, None, "physical", "list", None
    )


def test_quarantined_circuit_is_reported_not_fatal(
    suite_circuits, cha_cache, direct
):
    with faults.injected(
        faults.FaultRule("cha.backend", "raise", match=":bar")
    ):
        out = run_sweep(
            suite_circuits, journal_dir=None, shard_size=2,
            sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
        )
    assert set(out.failures) == {"bar"}
    assert out.circuits == tuple(c for c in CIRCUITS if c != "bar")
    assert_selection_equal(
        out.selection, direct, circuits=out.circuits, ref_names=CIRCUITS
    )


def test_hypothesis_shard_boundary_parity(suite_circuits, cha_cache, direct):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(shard_size=st.integers(min_value=1, max_value=len(CIRCUITS) + 1))
    def prop(shard_size):
        out = run_sweep(
            suite_circuits, journal_dir=None, shard_size=shard_size,
            sram_list=TOPOS, recipes=RECIPES, cache=cha_cache, n_jobs=1,
        )
        assert_selection_equal(out.selection, direct)

    prop()


# ---------------------------------------------------------------------------
# The real thing: SIGKILL a subprocess sweep mid-shard, resume, compare.
# ---------------------------------------------------------------------------


CLI_ARGS = [
    "--circuits", "adder,bar,max", "--scale", "tiny",
    "--recipes", ";Rw", "--topos", "3",
]


def _cli(journal, out, shard_size, cache, **popen_kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]
    )
    env.pop("REPRO_FAULTS", None)
    return subprocess.Popen(
        [sys.executable, "-m", "repro.core.sweep_runner",
         "--journal", str(journal), "--out", str(out),
         "--shard-size", str(shard_size), "--cache", str(cache), *CLI_ARGS],
        env=env, **popen_kw,
    )


@pytest.mark.slow
def test_sigkill_mid_sweep_then_resume_bit_identical(tmp_path):
    journal = tmp_path / "j"
    cache = tmp_path / "cha"
    killed_out = tmp_path / "killed.npz"

    # Launch a 3-shard sweep and SIGKILL it the moment shard 0 publishes.
    proc = _cli(
        journal, killed_out, 1, cache,
        stdout=subprocess.PIPE, text=True,
    )
    try:
        deadline = time.time() + 300
        line = ""
        while time.time() < deadline:
            line = proc.stdout.readline()
            if "shard 0 done" in line:
                break
        assert "shard 0 done" in line, "sweep never published a shard"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    assert not killed_out.exists()
    published = len(CheckpointManager(str(journal)).steps())
    assert 1 <= published < 3

    # Resume to completion; run an uninterrupted single-shard reference.
    resumed_out = tmp_path / "resumed.npz"
    assert _cli(journal, resumed_out, 1, cache).wait(600) == 0
    ref_out = tmp_path / "ref.npz"
    assert _cli(tmp_path / "j2", ref_out, 3, cache).wait(600) == 0

    a, b = np.load(resumed_out), np.load(ref_out)
    assert int(a["shards_resumed"]) >= 1
    for key in b.files:
        if key in ("shards_run", "shards_resumed"):
            continue
        assert np.array_equal(a[key], b[key]), key
