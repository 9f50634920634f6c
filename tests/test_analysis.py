"""The jit-discipline static analyzer (repro.analysis).

Four layers of coverage:

  * every lint rule fires on exactly its seeded-violation fixture
    (tests/lint_fixtures/, one file per rule) and nowhere else in it;
  * the real ``src/`` tree is clean — AST layer over the whole tree,
    jaxpr layer over every registered kernel — against an *empty*
    baseline, so new violations fail immediately;
  * the guards actually guard: stripping one ``# repro: host-boundary``
    annotation or one ``TRACE_COUNTS[...] += 1`` increment from a copy
    of a kernel module flips the lint to failing;
  * the registry unification keeps the historical public API: the
    per-module ``TRACE_COUNTS`` / ``trace_counts()`` names alias one
    shared Counter with module-scoped views.

Plus the regression the analyzer surfaced while being built:
`select_best_batch_device` used to force its operands through
``np.asarray``, materializing the service's device-resident (V, N)
re-rank tensors per request; it must keep jax arrays on device.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from repro.analysis import ast_lint, lint, registry
from repro.analysis.findings import (
    Finding,
    load_baseline,
    split_baselined,
    write_baseline,
)
from repro.analysis.jaxpr_lint import lint_kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
SRC = os.path.join(REPO, "src")


def fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


# ---------------------------------------------------------------------------
# Every rule fires on its fixture — and only its rule
# ---------------------------------------------------------------------------

AST_FIXTURES = {
    "fx_ast_host_sync.py": "ast-host-sync-unannotated",
    "fx_ast_host_sync_in_jit.py": "ast-host-sync-in-jit",
    "fx_ast_truthy_table.py": "ast-truthy-table",
    "fx_ast_jit_no_counter.py": "ast-jit-no-counter",
}

JAXPR_FIXTURES = {
    "fx_jaxpr_dtype_drift.py": "jaxpr-dtype-drift",
    "fx_jaxpr_host_callback.py": "jaxpr-host-callback",
    "fx_jaxpr_baked_const.py": "jaxpr-baked-const",
    "fx_jaxpr_static_unhashable.py": "jaxpr-static-unhashable",
    "fx_jaxpr_counter_missing.py": "jaxpr-counter-missing",
    "fx_jaxpr_donate_cpu.py": "jaxpr-donate-cpu",
}


@pytest.mark.parametrize("name,rule", sorted(AST_FIXTURES.items()))
def test_ast_rule_fires_exactly_once(name, rule):
    findings = ast_lint.lint_paths([fixture(name)], root=REPO)
    assert [f.rule for f in findings] == [rule]
    f = findings[0]
    assert f.severity == "error"
    assert f.line > 0
    assert "VIOLATION" in f.context


@pytest.mark.parametrize("name,rule", sorted(JAXPR_FIXTURES.items()))
def test_jaxpr_rule_fires_exactly_once(name, rule):
    findings = lint_kernels([fixture(name)])
    assert [f.rule for f in findings] == [rule]
    assert findings[0].severity == "error"


@pytest.mark.parametrize("name", sorted(AST_FIXTURES))
def test_cli_fails_on_ast_fixture(name, capsys):
    rc = lint.main(["--no-jaxpr", "--baseline", "", fixture(name)])
    assert rc == 1
    assert AST_FIXTURES[name] in capsys.readouterr().out


@pytest.mark.parametrize("name", sorted(JAXPR_FIXTURES))
def test_cli_fails_on_jaxpr_fixture(name, capsys):
    rc = lint.main(
        ["--no-ast", "--baseline", "", "--kernels-from", fixture(name)]
    )
    assert rc == 1
    assert JAXPR_FIXTURES[name] in capsys.readouterr().out


# ---------------------------------------------------------------------------
# The real tree is clean
# ---------------------------------------------------------------------------


def test_src_tree_ast_clean():
    findings = ast_lint.lint_paths([SRC], root=REPO)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_registered_kernels_jaxpr_clean():
    findings = lint_kernels()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_all_kernel_modules_register():
    specs = registry.kernel_specs()
    by_module = {}
    for s in specs:
        by_module.setdefault(s.module, []).append(s.name)
    assert sorted(by_module.get("repro.core.batch", [])) == [
        "fused_suite", "select_batch",
    ]
    assert sorted(by_module.get("repro.kernels.aig_sim", [])) == [
        "aig_eval", "aig_sig",
    ]
    assert by_module.get("repro.launch.system") == ["roofline_sweep"]
    # the Pallas kernels register counters (AST-enforced), not specs
    assert registry.KERNEL_OWNERS["aig_eval_pallas"] == "repro.kernels.aig_sim"
    assert registry.KERNEL_OWNERS["cim_pallas"] == "repro.kernels.cim_logic"


def test_batch_registers_one_sweep_program():
    """`core/batch.py` owns exactly two jitted programs: the fused sweep
    that every exploration runs and the standalone selection filter."""
    registry.kernel_specs()  # imports every kernel module
    owned = {k for k, m in registry.KERNEL_OWNERS.items() if m == "repro.core.batch"}
    assert owned == {"fused_suite", "select_batch"}


# ---------------------------------------------------------------------------
# The guards guard: stripping an annotation / a counter line flips to red
# ---------------------------------------------------------------------------


def _strip_one(source: str, needle: str) -> str:
    assert needle in source
    return source.replace(needle, "", 1)


def test_flip_removing_host_boundary_annotation(tmp_path):
    src = open(os.path.join(SRC, "repro", "core", "batch.py")).read()
    clean = ast_lint.lint_paths([os.path.join(SRC, "repro", "core", "batch.py")])
    assert clean == []
    stripped = tmp_path / "batch_stripped.py"
    # drop one trailing-comment annotation (whole comment, keep the code)
    needle = "  # repro: host-boundary\n"
    assert needle in src
    stripped.write_text(src.replace(needle, "\n", 1))
    findings = ast_lint.lint_paths([str(stripped)])
    assert any(f.rule == "ast-host-sync-unannotated" for f in findings)


def test_flip_removing_trace_count_increment(tmp_path):
    src = open(os.path.join(SRC, "repro", "core", "batch.py")).read()
    stripped = tmp_path / "batch_stripped.py"
    stripped.write_text(
        _strip_one(src, 'TRACE_COUNTS["select_batch"] += 1')
    )
    findings = ast_lint.lint_paths([str(stripped)])
    assert any(f.rule == "ast-jit-no-counter" for f in findings)


def test_no_trace_count_optout(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "@jax.jit  # repro: no-trace-count\n"
        "def helper(x):\n"
        "    return jnp.sin(x)\n"
    )
    assert ast_lint.lint_paths([str(p)]) == []


def test_host_boundary_annotation_suppresses(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(
        "# repro: kernel-module\n"
        "import numpy as np\n"
        "\n"
        "def gather(grid):\n"
        "    dev = grid._raw('energy')\n"
        "    return np.asarray(dev)  # repro: host-boundary\n"
    )
    assert ast_lint.lint_paths([str(p)]) == []


def test_truthiness_on_mapping_of_tables_is_fine(tmp_path):
    # Mapping[str, WorkloadTable] is a dict; `if not works` is idiomatic
    p = tmp_path / "m.py"
    p.write_text(
        "from typing import Mapping\n"
        "\n"
        "def f(works: 'Mapping[str, WorkloadTable]'):\n"
        "    if not works:\n"
        "        raise ValueError('empty')\n"
    )
    assert ast_lint.lint_paths([str(p)]) == []


# ---------------------------------------------------------------------------
# Registry unification: one Counter, historical per-module views
# ---------------------------------------------------------------------------


def test_trace_counter_aliases_share_one_counter():
    from repro.core import batch
    from repro.kernels import aig_sim, cim_logic
    from repro.launch import system

    assert batch.TRACE_COUNTS is registry.TRACE_COUNTS
    assert aig_sim.TRACE_COUNTS is registry.TRACE_COUNTS
    assert cim_logic.TRACE_COUNTS is registry.TRACE_COUNTS
    assert system.TRACE_COUNTS is registry.TRACE_COUNTS


def test_trace_counts_views_are_module_scoped():
    from repro.core import batch
    from repro.kernels import aig_sim

    registry.TRACE_COUNTS["aig_eval"] += 1
    try:
        assert "aig_eval" not in batch.trace_counts()
        assert "aig_eval" in aig_sim.trace_counts()
        assert "aig_eval" in registry.trace_counts()  # global view
        # batch's view only ever carries batch-owned keys
        assert all(
            registry.KERNEL_OWNERS[k] == "repro.core.batch"
            for k in batch.trace_counts()
        )
    finally:
        registry.TRACE_COUNTS["aig_eval"] -= 1


def test_counter_ownership_conflict_rejected():
    with pytest.raises(ValueError, match="already registered"):
        registry.register_counter("fused_suite", "some.other.module")


# ---------------------------------------------------------------------------
# Baseline mechanism
# ---------------------------------------------------------------------------


def test_baseline_roundtrip_and_line_independence(tmp_path):
    f = Finding(
        rule="ast-truthy-table", severity="error", path="src/x.py",
        line=3, message="m", context="return model or DEFAULT",
    )
    path = str(tmp_path / "baseline.json")
    write_baseline(path, [f])
    baseline = load_baseline(path)
    moved = dataclasses.replace(f, line=99)  # edits move code around
    fresh = dataclasses.replace(f, rule="ast-jit-no-counter")
    new, old = split_baselined([moved, fresh], baseline)
    assert old == [moved]
    assert new == [fresh]


def test_cli_write_baseline_then_green(tmp_path, capsys):
    target = fixture("fx_ast_truthy_table.py")
    bl = str(tmp_path / "bl.json")
    assert lint.main(["--no-jaxpr", "--baseline", bl, target]) == 1
    assert (
        lint.main(
            ["--no-jaxpr", "--baseline", bl, "--write-baseline", target]
        )
        == 0
    )
    capsys.readouterr()
    assert lint.main(["--no-jaxpr", "--baseline", bl, target]) == 0
    out = capsys.readouterr().out
    assert "[baselined]" in out


def test_cli_json_format(capsys):
    rc = lint.main(
        ["--no-jaxpr", "--baseline", "", "--format", "json",
         fixture("fx_ast_jit_no_counter.py")]
    )
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"]["new"] == 1
    assert payload["new"][0]["rule"] == "ast-jit-no-counter"


def test_checked_in_baseline_is_empty():
    # the repo tree must be *actually* clean, not grandfathered-clean
    path = os.path.join(SRC, "repro", "analysis", "baseline.json")
    assert json.load(open(path)) == []


# ---------------------------------------------------------------------------
# Regression: device-resident re-rank operands stay on device
# ---------------------------------------------------------------------------


def test_select_best_batch_device_keeps_operands_on_device(monkeypatch):
    from repro.core import batch as B

    from repro.runtime import jax_env

    B._load_jax()
    rng = np.random.default_rng(7)
    host_energy = rng.random((4, 96))
    host_fits = np.ones((1, 96), dtype=bool)
    with jax_env.x64():
        energy = B.jnp.asarray(host_energy)
        fits = B.jnp.asarray(host_fits)

    materialized = []
    orig_asarray = np.asarray

    def spy(a, *args, **kwargs):
        if isinstance(a, B.jax.Array) and a.size >= 96:
            materialized.append(np.shape(a))
        return orig_asarray(a, *args, **kwargs)

    monkeypatch.setattr(np, "asarray", spy)
    try:
        idx = B.select_best_batch_device(energy, fits)
    finally:
        monkeypatch.undo()

    assert materialized == [], (
        "select_best_batch_device materialized device tensors on host: "
        f"{materialized}"
    )
    expected = B.select_best_batch(host_energy, host_fits)
    np.testing.assert_array_equal(np.asarray(idx), expected)
