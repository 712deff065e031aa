"""A cell's layout comes from its chips in BENCHMARK.json: on four chips
the batch is split over them through the trainer's mesh, with the banked
store sharded over them (``zero1``). On four forced host devices
the tiny cell is correct, its shares of a peak count the four chips, and
a timed path broken underneath (the exchange between chips left out, half
the batch left out, the state left unchanged) is not correct."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import harness
import tiny

BENCH = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent('''
    import contextlib, io, json, re, sys
    from pathlib import Path
    sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
    import jax
    import calibrate, harness, run, tiny, train
    from repro.methods.selection import SelectionMethod

    def one(root, fault=None):
        out, err = io.StringIO(), io.StringIO()
        make = SelectionMethod.make_step
        if fault is not None:
            def broken(self, *a, **kw):
                step = make(self, *a, **kw)
                return fault(step)
            SelectionMethod.make_step = broken
        try:
            with contextlib.redirect_stdout(out), \\
                    contextlib.redirect_stderr(err):
                run.main(["--workload", tiny.CELL, "--seed", "3000000041",
                          "--seconds", "1.5", "--trace", "1"], root=root,
                         devices=jax.devices())
        finally:
            SelectionMethod.make_step = make
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        window = float(re.search(r"\\[window\\] \\d+ steps in ([0-9.]+)s",
                                 err.getvalue()).group(1))
        return {{"line": line, "window_s": window}}

    def unchanged(step):
        def wrapped(state, batch):
            keep = jax.tree.map(
                lambda x: x.copy() if hasattr(x, "copy") else x, state)
            _, metrics = step(state, batch)
            return keep, metrics
        wrapped.__dict__.update(step.__dict__)
        return wrapped

    tmp = Path(sys.argv[1])
    dp4 = lambda name: tiny.make_root(tmp / name, chips=4)
    res = {{"dp4": one(dp4("dp4")), "dp1": one(tiny.make_root(tmp / "dp1")),
           "no_exchange": one(dp4("nx"), lambda s: calibrate.rows_kept(s, 4)),
           "half_batch": one(dp4("hb"), lambda s: calibrate.rows_kept(s, 2)),
           "state_unchanged": one(dp4("su"), unchanged)}}
    mesh = lambda name: train.data_mesh(
        harness.load_cell(tiny.CELL, tmp / name), jax.devices())
    res["mesh"] = {{k: None if mesh(k) is None else dict(mesh(k).shape)
                   for k in ("dp4", "dp1")}}
    print(json.dumps(res))
''')


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("layout")
    code = SCRIPT.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                         src=str(BENCH.parent / "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code, str(tmp)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chips_are_correct_and_reported(runs):
    line = runs["dp4"]["line"]
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    assert line["checks"]["blocks_restored"]["value"] >= 1
    assert line["metrics"]["window_compiles"]["value"] == 0


def test_shares_count_every_chip(runs):
    """The same seed trains the same tokens on one chip and on four; over
    four chips' peak, the FLOPs a second read a quarter as much."""
    m4, m1 = (runs[k]["line"]["metrics"]["train_mfu"]["value"]
              for k in ("dp4", "dp1"))
    assert runs["dp4"]["line"]["attempted"] == \
        runs["dp1"]["line"]["attempted"]
    flops4 = m4 * runs["dp4"]["window_s"] * 4
    flops1 = m1 * runs["dp1"]["window_s"]
    assert flops4 == pytest.approx(flops1, rel=1e-9)


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch",
                                   "state_unchanged"])
def test_a_broken_timed_path_on_four_chips_is_not_correct(runs, fault):
    line = runs[fault]["line"]
    assert line["correct"] is False, line["checks"]


def test_the_cells_chips_make_its_mesh(runs):
    """Four chips give the program's data-parallel mesh over them, one
    chip no mesh at all."""
    assert runs["mesh"] == {"dp4": {"data": 4, "model": 1}, "dp1": None}


def test_every_seed_of_the_dp4_cell_makes_the_same_selections():
    """The dp4 cell fixes the program's selection seed, so every seed's
    boundary moves the same blocks; the checked steps still explore,
    exploit, explore, and the window exploits 27 of its 76 steps."""
    import numpy as np

    import train
    cell = harness.load_cell("qwen2.5-0.5b.train.ags_banked_dp4")
    chosen = cell.workload["selection_seed"]
    assert {train.program_seed(cell, s)
            for s in (0, 7, 2 ** 40 + 3)} == {chosen}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    steps = round(bench["run_seconds"] / cell.workload["step_s"])
    ex = train._explores(np.array([chosen]), cell.traffic["job"]["select"],
                         3 + steps)[0]
    assert ex[0] and not ex[1] and ex[2]
    assert (steps, int(np.sum(~ex[3:]))) == (76, 27)
