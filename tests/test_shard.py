"""The variant-sharded fused sweep on four emulated devices.

`explore_suite(shard=None)` lays the variant axis over every visible
device (`batch._shard_variants`).  A subprocess with four CPU devices
(``--xla_force_host_platform_device_count=4``) runs this file as a
script: three default-width circuits x 12 topologies x the recipes of
length <= 2, V=8 Monte-Carlo variants.  It sweeps them sharded and
unsharded (``shard=False``), runs the scalar ``explore(backend="python")``
on sampled variants' models, sweeps V=6 (indivisible by four: three
devices), and records the program spans of sharded calls.  The tests
compare what it prints:

  * sharded winners, winner energies, nominal-winner latencies and
    capacity flags are bit-identical to the unsharded path's;
  * sampled variants' winners are the scalar path's, energies within
    1e-12 relative;
  * V=6 runs on three devices and stays exact;
  * lazy views of the sharded grid, composed ones among them, dispatch
    no slice when made and materialize bit-identical to the unsharded
    grid's, and a view's dispatched slice spans the four devices;
  * ``rcim.batch.dispatch``/``.shard``/``.fetch`` carry the devices and
    the variants per device, and ``placed_bytes`` counts each operand
    the program is given once per device it reaches.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
DEVICES = 4
V = 8
V_ODD = 6
SAMPLED = (0, 3, 7)
#: Rounding of the scalar path's float64 arithmetic against the batched
#: kernel's: a few ulps, far below any gap between two designs.
SCALAR_REL_TOL = 1e-12
LAZY_KEYS = ("cycles", "active_macro_cycles", "fits", "latency_ns", "energy_nj",
             "power_mw", "throughput_gops", "tops_per_watt")
#: Lazy views of the suite grid, composed ones among them.
VIEWS = {
    "variation": lambda g: g.variation("max"),
    "variation.grid": lambda g: g.variation("max").grid(3),
    "suite": lambda g: g.suite(5),
    "suite.grid": lambda g: g.suite(5).grid("log2"),
}


def _explore_fields(res: dict) -> dict:
    return {name: dict(
        winners=[[list(r), t.name] for r, t in r_.variation.winners],
        energy=[float(e).hex() for e in r_.variation.winner_energy_nj],
    ) for name, r_ in res.items()}


def _selection_fields(sel) -> dict:
    return dict(
        winner_idx=sel.winner_idx.tolist(),
        winner_energy=[float(e).hex() for e in sel.winner_energy_nj.ravel()],
        nominal_latency_ns=[float(x).hex() for x in sel.nominal_latency_ns.ravel()],
        nominal_fits=sel.nominal_fits.tolist(),
    )


def _spans(trace_dir: Path) -> dict:
    """The arguments of every ``rcim.batch.*`` event of one trace."""
    import glob

    import jax

    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    out: dict[str, list] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("rcim.batch."):
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def _measure(tmp: Path) -> dict:
    """Everything the tests compare, computed on four devices."""
    import jax
    import numpy as np

    from repro.core import batch, circuits as C
    from repro.core.explorer import explore, explore_suite
    from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable
    from repro.core.transforms import characterize_suite, enumerate_recipes

    assert len(jax.devices()) == DEVICES, jax.devices()
    suite = {"bar": C.gen_barrel_shifter(), "max": C.gen_max(), "log2": C.gen_log2()}
    recipes = [r for r in enumerate_recipes() if len(r) <= 2]
    cha = characterize_suite(suite, recipes, n_jobs=1, backend="python")
    tables = (batch.SuiteTable.from_cha(
        {n: {r: cha[n][r] for r in [(), *recipes]} for n in suite}),
        batch.TopologyTable.from_topologies(TOPOLOGY_LIBRARY))
    out: dict = {}

    def sweep(table, shard):
        res = explore_suite(suite, TOPOLOGY_LIBRARY, recipes, cha=cha,
                            model_sweep=table, shard=shard)
        _, sel = batch.evaluate_select_suite(*tables, table, shard=shard)
        return dict(explore=_explore_fields(res), selection=_selection_fields(sel),
                    sharded=sel.sharded)

    table = ModelTable.monte_carlo(n=V, sigma=0.2, seed=16)
    odd = ModelTable.monte_carlo(n=V_ODD, sigma=0.2, seed=61)
    out["sharded"], out["plain"] = sweep(table, None), sweep(table, False)
    out["odd_sharded"], out["odd_plain"] = sweep(odd, None), sweep(odd, False)
    out["scalar"] = {
        str(v): {name: (lambda b: dict(winner=[list(b.recipe), b.topo.name],
                                        energy=b.metrics.energy_nj))(
            explore(rtl, TOPOLOGY_LIBRARY, recipes, model=table.model(v),
                    backend="python", cha=cha[name]).best)
                 for name, rtl in suite.items()}
        for v in SAMPLED}

    # composed lazy views over the variant-sharded grid against the same
    # views of the unsharded one; making them dispatches no slice
    sharded, _ = batch.evaluate_select_suite(*tables, table, shard=None)
    plain, _ = batch.evaluate_select_suite(*tables, table, shard=False)
    out["views"] = {}
    for name, make in VIEWS.items():
        made = dict(batch.VIEW_COUNTS)
        view, ref = make(sharded), make(plain)
        made_views = batch.VIEW_COUNTS["views"] - made["views"]
        made_slices = batch.VIEW_COUNTS["slices"] - made["slices"]
        devices = len(view._raw("energy_nj").sharding.device_set)
        out["views"][name] = dict(
            made_views=made_views, made_slices=made_slices, devices=devices,
            fields={k: [str(getattr(view, k).dtype), getattr(view, k).shape,
                        getattr(view, k).tobytes() == getattr(ref, k).tobytes()]
                    for k in LAZY_KEYS},
            ref={k: [str(getattr(ref, k).dtype), getattr(ref, k).shape]
                 for k in LAZY_KEYS})

    # the operands the fused program is given, by placement: host arrays
    # (replicated to every device) and arrays already laid over devices
    fused = batch._fused_program()
    given = []

    def counted(*args, **kw):
        leaves = jax.tree_util.tree_leaves(args)
        given.append(dict(
            host=sum(x.nbytes for x in leaves if isinstance(x, (np.ndarray, np.generic))),
            placed=sum(x.nbytes for x in leaves if isinstance(x, jax.Array)),
        ))
        return fused(*args, **kw)

    batch._FUSED_SUITE = counted
    for name, t in (("spans", table), ("odd_spans", odd)):
        trace_dir = tmp / name
        jax.profiler.start_trace(str(trace_dir))
        try:
            explore_suite(suite, TOPOLOGY_LIBRARY, recipes, cha=cha, model_sweep=t)
        finally:
            jax.profiler.stop_trace()
        out[name] = dict(_spans(trace_dir), given=given[-1])
    batch._FUSED_SUITE = fused
    return out


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("shard")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={DEVICES}")
    proc = subprocess.run([sys.executable, __file__, str(tmp)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("part,field", [
    ("explore", "winners"), ("explore", "energy"),
    ("selection", "winner_idx"), ("selection", "winner_energy"),
    ("selection", "nominal_latency_ns"), ("selection", "nominal_fits"),
])
def test_sharded_is_bit_identical_to_unsharded(four, part, field):
    assert four["sharded"]["sharded"] and not four["plain"]["sharded"]
    got, want = four["sharded"][part], four["plain"][part]
    if part == "explore":
        got = {n: r[field] for n, r in got.items()}
        want = {n: r[field] for n, r in want.items()}
    else:
        got, want = got[field], want[field]
    assert got == want


@pytest.mark.parametrize("v", SAMPLED)
def test_sampled_variant_matches_the_scalar_path(four, v):
    for name, one in four["scalar"][str(v)].items():
        res = four["sharded"]["explore"][name]
        assert res["winners"][v] == one["winner"]
        got = float.fromhex(res["energy"][v])
        assert abs(got - one["energy"]) <= SCALAR_REL_TOL * abs(one["energy"])


def test_indivisible_variants_fall_back_to_three_devices_exactly(four):
    assert four["odd_sharded"]["sharded"]
    assert four["odd_sharded"]["explore"] == four["odd_plain"]["explore"]
    assert four["odd_sharded"]["selection"] == four["odd_plain"]["selection"]
    (shard,) = four["odd_spans"]["rcim.batch.shard"]
    assert shard == {"devices": 3, "variants_per_device": V_ODD // 3}


@pytest.mark.parametrize("view", VIEWS)
def test_composed_views_over_sharded_arrays_are_bit_identical(four, view):
    got = four["views"][view]
    # one deferred view a level for each of the two grids, no slice
    assert got["made_views"] == 2 * (view.count(".") + 1)
    assert got["made_slices"] == 0
    assert all(same for _, _, same in got["fields"].values())
    assert {k: f[:2] for k, f in got["fields"].items()} == got["ref"]
    # the one slice `_raw` dispatches still spans the four devices
    assert got["devices"] == DEVICES


@pytest.mark.parametrize("key,devices", [("spans", DEVICES), ("odd_spans", 3)])
def test_spans_count_devices_and_placed_bytes(four, key, devices):
    spans = four[key]
    (dispatch,), (shard,), (fetch,) = (spans[f"rcim.batch.{n}"]
                                       for n in ("dispatch", "shard", "fetch"))
    given = spans["given"]
    assert shard["devices"] == dispatch["devices"] == fetch["devices"] == devices
    assert shard["variants_per_device"] * devices == (V if key == "spans" else V_ODD)
    # the model operands arrive laid over the devices: counted once;
    # the host tables are replicated: counted once per device
    assert given["placed"] > 0
    assert dispatch["h2d_bytes"] == given["host"] + given["placed"]
    assert dispatch["placed_bytes"] == given["host"] * devices + given["placed"]
    assert fetch["d2h_bytes"] > 0


if __name__ == "__main__":
    print(json.dumps(_measure(Path(sys.argv[1]))))
