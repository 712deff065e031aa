"""The harness end to end on the CPU at a tiny size: cells, traffic mixes
and per-layer metrics are found by name; a run without a TPU prints no
result; and a run whose timed path is broken comes out not correct."""
import json
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import harness
import run
import tiny


def _run(root, capsys, seed=2 ** 31 + 5, trace=0, cell=tiny.CELL,
         seconds=0.3):
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    root=root, devices=jax.devices()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_new_cell_and_metric_are_found_by_adding_files(tmp_path, capsys):
    root = tiny.make_root(tmp_path)
    # a per-layer metric added as one file and one BENCHMARK.json entry
    (root / "bench" / "metrics" / "window_steps.py").write_text(
        "def read(ctx):\n    return ctx['steps']\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "window_steps", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "training step", "moves":
                           "train_tokens_per_s", "workloads": [tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = harness.load_cell(tiny.CELL, root)
    assert cell.kind == "train" and cell.config["hidden_size"] == 64
    assert "window_steps" in [m["name"] for m in cell.per_layer]
    line = _run(root, capsys, trace=1)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["window_steps"]["value"] == line["attempted"]
    assert line["metrics"]["train_mfu"]["unit"] == "%"
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path, capsys):
    line = _run(tiny.make_root(tmp_path, method="full", residency="device"),
                capsys)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s",
                                    "train_peak_hbm_gib", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def test_without_a_tpu_or_the_program_there_is_no_result(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2.5-0.5b.train.full", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def _unchanged(step):
    """A step that returns its state unchanged."""
    def wrapped(state, batch):
        keep = jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x,
                            state)
        _, metrics = step(state, batch)
        return keep, metrics
    return wrapped


def _half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def wrapped(state, batch):
        mask = np.array(batch["loss_mask"])
        mask[mask.shape[0] // 2:] = 0
        return step(state, {**batch, "loss_mask": mask})
    return wrapped


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch,
                                            fault):
    from repro.methods.selection import SelectionMethod
    make = SelectionMethod.make_step

    def broken(self, *a, **kw):
        step = make(self, *a, **kw)
        out = fault(step)
        out.__dict__.update(getattr(step, "__dict__", {}))
        return out

    monkeypatch.setattr(SelectionMethod, "make_step", broken)
    root = tiny.make_root(tmp_path, residency="device")
    line = _run(root, capsys)
    assert line["correct"] is False, line["checks"]


def test_a_lost_restore_is_not_correct(tmp_path, capsys):
    """Blocks evicted from the bank come back with zero moments when the
    boundary's write-back is left out: the checked steps see it."""
    import calibrate
    root = tiny.make_root(tmp_path)
    with calibrate.restore_lost():
        line = _run(root, capsys)
    assert line["checks"]["blocks_restored"]["value"] >= 1
    assert line["correct"] is False, line["checks"]


def test_the_checked_steps_evict_and_restore(tmp_path, capsys):
    """The banked cell fixes the program's selection seed, so every seed
    makes the same selections: its checked steps explore, exploit,
    explore (a block leaves the bank and comes back), and its window
    exploits 32 of its 89 steps."""
    import train
    cell = harness.load_cell("qwen2.5-0.5b.train.ags_banked")
    chosen = cell.workload["selection_seed"]
    assert 0 <= chosen < 2 ** 31
    assert {train.program_seed(cell, s)
            for s in (0, 7, 2 ** 31 + 5, 2 ** 40 + 3)} == {chosen}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    steps = round(bench["run_seconds"] / cell.workload["step_s"])
    ex = train._explores(np.array([chosen]), cell.traffic["job"]["select"],
                         train.CHECK_STEPS + steps)[0]
    assert ex[0] and not ex[1] and ex[2]
    assert (steps, int(np.sum(~ex[train.CHECK_STEPS:]))) == (89, 32)
    line = _run(tiny.make_root(tmp_path / "run"), capsys)
    assert line["checks"]["blocks_restored"]["value"] >= 1, line["checks"]


def test_the_fp8_control_is_not_correct(tmp_path):
    """The reference computed in fp8 in the program's place fails at least
    one of the cell's numbers against the f32 reference."""
    import train
    cell = harness.load_cell(tiny.CELL, tiny.make_root(tmp_path))
    batches = train.first_batches(train.make_feed(cell, 3))
    ref = train.reference_readings(cell, 3, batches)
    ctl = train.reference_readings(cell, 3, batches, mode="fp8")
    checks = train._compare(ctl, ref, cell.workload["limits"], cell)
    assert not all(c.ok for c in checks), checks

