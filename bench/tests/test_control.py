"""The correctness checks can fail: each cell's control, and each fault the
cell can have planted under the timed path, read as not correct; the
program as it is reads correct.  Small sizes, on the CPU, with the
harness's look for a chip skipped."""

from __future__ import annotations

import contextlib
import json
import time

import numpy as np
import pytest

import control
from conftest import ROOT, SERVICE, run_cell, run_mix

SEED = 2**31 + 101
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS + [SERVICE["name"]])
def test_control_is_not_correct(layout, cell):
    import run

    run._jax_env(layout)
    out = control.readings(layout, SERVICE if cell == SERVICE["name"] else cell, SEED, 3.0)
    assert any(v > lim for v, lim in out.values()), out


# -- faults planted in the program --------------------------------------------


def _shift_winners(monkeypatch):
    """The fused selection answers the next design over: an answer
    altered where it is produced."""
    import dataclasses

    from repro.core import explorer

    orig = explorer.evaluate_select_suite

    def fused(*a, **kw):
        sg, sel = orig(*a, **kw)
        n = len(sg.topologies) * len(sg.recipes)
        return sg, dataclasses.replace(sel, winner_idx=(sel.winner_idx + 1) % n)

    monkeypatch.setattr(explorer, "evaluate_select_suite", fused)


def _half_the_variants(monkeypatch):
    """Only the first half of the variants is evaluated; the rest repeat
    it: half of the batch left out."""
    from repro.core import explorer
    from repro.core.sram import ModelTable

    orig = explorer.evaluate_select_suite

    def fused(suite, topos, model, *a, **kw):
        if isinstance(model, ModelTable) and len(model) > 1:
            half = -(-len(model) // 2)
            rows = np.arange(len(model)) % half
            model = ModelTable(names=model.names, **{
                f: getattr(model, f)[rows] for f in (
                    "f_clk_hz", "e_op_fj", "e_op_marginal_fj",
                    "writeback_fj_nonresonant", "resonance_recycle_eta",
                    "p_ctrl_mw", "e_macro_cycle_fj", "e_col_cycle_fj",
                    "alpha_mw_per_level", "bitcell_um2", "periphery_overhead",
                    "pipeline_utilization")})
        return orig(suite, topos, model, *a, **kw)

    monkeypatch.setattr(explorer, "evaluate_select_suite", fused)


def _rerank_next_design(monkeypatch):
    """The service's re-rank answers the next design over."""
    from repro.serve import explore_service

    orig = explore_service.select_best_batch_device

    def select(energy, *a, **kw):
        idx = np.asarray(orig(energy, *a, **kw))
        return (idx + 1) % energy.shape[-1]

    monkeypatch.setattr(explore_service, "select_best_batch_device", select)


def _half_of_each_batch(monkeypatch):
    """Half of each service batch left out: the second half is answered
    with an error instead of being served."""
    from repro.serve import explore_service

    orig = explore_service.ExplorationService._process

    def process(self, batch):
        keep = batch[: -(-len(batch) // 2)]
        orig(self, keep)
        for p in batch[len(keep):]:
            if p.future.set_running_or_notify_cancel():
                p.error = explore_service.ServiceError("internal", "left out")
                self._resolve(p, time.perf_counter())

    monkeypatch.setattr(explore_service.ExplorationService, "_process", process)


def _flip_truth_tables(monkeypatch):
    """Every cone truth table comes back with its lowest bit flipped: an
    answer altered where it is produced."""
    from repro.kernels import aig_sim

    orig = aig_sim.eval_tts

    def eval_tts(*a, **kw):
        return [tuple(t ^ 1 for t in tts) for tts in orig(*a, **kw)]

    monkeypatch.setattr(aig_sim, "eval_tts", eval_tts)


def _half_of_the_queries(monkeypatch):
    """Only the first half of each batch of cone queries is simulated;
    the rest get those answers again: half of the batch left out."""
    from repro.kernels import aig_sim

    orig = aig_sim.eval_tts

    def eval_tts(aig, items, *a, **kw):
        half = -(-len(items) // 2)
        kw.pop("members", None)
        out = orig(aig, items[:half], *a, **kw)
        return [out[i % half] if len(out[i % half]) == len(items[i][0])
                else out[0][:1] * len(items[i][0]) for i in range(len(items))]

    monkeypatch.setattr(aig_sim, "eval_tts", eval_tts)


def _miscount_nands(monkeypatch):
    """Every characterization records one NAND too many."""
    import dataclasses

    from repro.core.aig import Aig

    orig = Aig.characterize

    def characterize(self):
        s = orig(self)
        return dataclasses.replace(s, nand_count=s.nand_count + 1)

    monkeypatch.setattr(Aig, "characterize", characterize)


FAULTS = [
    ("sweep.lib12.mc16", _shift_winners, "winner_energy_rel_err"),
    ("sweep.lib12.mc16", _half_the_variants, "winner_energy_rel_err"),
    ("serve.lib12.rerank", _rerank_next_design, "winner_energy_rel_err"),
    ("serve.lib12.rerank", _half_of_each_batch, "wrong_answers"),
    ("cha.cut16k.cold", _flip_truth_tables, "inequivalent_apps"),
    ("cha.cut16k.cold", _half_of_the_queries, "inequivalent_apps"),
    ("cha.cut16k.cold", _miscount_nands, "stats_mismatches"),
    # control.py's faults, by name: transforms that return their input
    # unchanged (a step that returns its state unchanged), and that keep
    # half of their cuts (function kept, less optimized)
    ("cha.cut16k.cold", "no-op", "wrong_outputs"),
    ("cha.cut16k.cold", "half-cuts", "wrong_outputs"),
]


@pytest.mark.parametrize("cell,fault,number", FAULTS, ids=[
    f"{c}-{f if isinstance(f, str) else f.__name__.strip('_')}" for c, f, _ in FAULTS])
def test_fault_is_not_correct(layout, monkeypatch, cell, fault, number):
    if cell.startswith("serve"):
        # a higher rate, so that the service batches requests together
        p = layout / "bench/traffic/serve.rerank.json"
        p.write_text(json.dumps(dict(json.loads(p.read_text()), rate_per_s=400)))
    with contextlib.ExitStack() as stack:
        if isinstance(fault, str):
            stack.enter_context(control.FAULTS[fault]())
        else:
            fault(monkeypatch)
        if cell == SERVICE["name"]:
            checks = run_mix(layout, SERVICE, seconds=3.0, seed=SEED)
        else:
            checks = run_cell(layout, cell, seconds=3.0, seed=SEED)["checks"]
    c = checks[number]
    assert c["value"] > c["limit"], checks
