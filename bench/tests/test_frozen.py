"""The frozen data under bench/data is what it says it is: the netlists
are the program's generators at each configuration's widths, the frozen
characterization is what `characterize_suite` gives on the python
backend, and the expected outputs are what the benchmark's reference
transforms give, which are the program's python transforms' outputs too,
all at the current `TRANSFORM_VERSION`."""

from __future__ import annotations

import json

import pytest

import common
import freeze
import ref_transforms
import reference as ref
from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: common.load_json(ROOT / c["file"]) for c in SPEC["configs"]}
#: Circuits whose frozen entries the tests make again (the smallest two).
CHECKED = ("adder", "log2")


def _with(key: str) -> list[str]:
    return [n for n, c in CONFIGS.items() if key in c]


def _version_moved(frozen: dict) -> str:
    from repro.core.transforms import TRANSFORM_VERSION

    return (f"bench/data holds data made at TRANSFORM_VERSION "
            f"{frozen['transform_version']}, the program is at {TRANSFORM_VERSION}: "
            f"the transforms changed, so the frozen data (bench/freeze.py) and "
            f"bench/ref_transforms.py must be made again")


@pytest.mark.parametrize("name", list(CONFIGS))
def test_netlists_are_the_configured_suite(name):
    from repro.core import circuits

    conf = CONFIGS[name]
    nets = common.load_json(ROOT / conf["netlists"])["circuits"]
    assert list(nets) == conf["circuits"]
    for c in conf["circuits"]:
        kw = {arg: conf[key] for key, arg in freeze.WIDTH_KEYS[c].items()}
        assert nets[c] == circuits._GENERATORS[c](**kw).to_dict(), c
        assert len(nets[c]["f0"]) - 1 - nets[c]["n_pis"] == conf["and_nodes"][c], c


@pytest.mark.parametrize("name", _with("frozen_cha"))
def test_frozen_characterization_matches_the_python_backend(name):
    from repro.core.aig import Aig
    from repro.core.transforms import TRANSFORM_VERSION, characterize_suite

    conf = CONFIGS[name]
    frozen = common.load_json(ROOT / conf["frozen_cha"])
    assert frozen["transform_version"] == TRANSFORM_VERSION, _version_moved(frozen)
    nets = common.load_json(ROOT / conf["netlists"])["circuits"]
    recipes = ref.recipes(conf["recipes"])
    got = characterize_suite({n: Aig.from_dict(nets[n]) for n in CHECKED},
                             recipes[1:], n_jobs=1, backend="python")
    for c in CHECKED:
        for r in recipes:
            assert got[c][r].to_dict() == frozen["circuits"][c][",".join(r)], (c, r)


@pytest.mark.parametrize("name", _with("frozen_cha"))
def test_reference_gate_stats_match_the_frozen_baseline(name):
    """The reference's own NAND2/NOR2/NOT mapping of each netlist equals
    its frozen baseline (recipe ``()``) statistics."""
    conf = CONFIGS[name]
    nets = common.load_json(ROOT / conf["netlists"])["circuits"]
    frozen = common.load_json(ROOT / conf["frozen_cha"])
    for c, d in nets.items():
        assert ref.gate_stats(d) == frozen["circuits"][c][""], c


@pytest.mark.parametrize("name", _with("warm_graphs"))
def test_warm_graphs_are_the_reference_depth1_outputs(name):
    """The graphs the characterization set-up warms are each circuit's
    outputs after one transform, as the frozen expected outputs name
    them."""
    conf = CONFIGS[name]
    warm = common.load_json(ROOT / conf["warm_graphs"])
    want = common.load_json(ROOT / conf["expected_outputs"])["circuits"]
    assert sorted(warm["circuits"]) == sorted(conf["circuits"])
    for c, per in warm["circuits"].items():
        assert sorted(per) == sorted(ref_transforms.TRANSFORMS), c
        for t, d in per.items():
            assert ref_transforms.Aig.from_dict(d).fingerprint() == want[c][t], (c, t)


@pytest.mark.parametrize("name", _with("expected_outputs"))
def test_expected_outputs_are_the_reference_transforms(name):
    """The reference transforms make the frozen expected outputs again,
    and the program's python transforms give the same structures."""
    from repro.core.aig import Aig
    from repro.core.transforms import TRANSFORM_VERSION, RecipeRunner

    conf = CONFIGS[name]
    frozen = common.load_json(ROOT / conf["expected_outputs"])
    assert frozen["transform_version"] == TRANSFORM_VERSION, _version_moved(frozen)
    assert ref_transforms.TRANSFORM_VERSION == TRANSFORM_VERSION, _version_moved(frozen)
    nets = common.load_json(ROOT / conf["netlists"])["circuits"]
    recipes = ref.recipes(conf["recipes"])
    for c in CHECKED:
        want = frozen["circuits"][c]
        assert ref_transforms.expected_outputs(nets[c], recipes) == want, c
        runner = RecipeRunner(Aig.from_dict(nets[c]), backend="python")
        for r in recipes:
            assert runner.run_fp(r) == want[",".join(r)], (c, r)
