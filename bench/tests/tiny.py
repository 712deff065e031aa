"""A benchmark root holding one tiny training cell, for CPU tests. Its
files are written next to a copy of BENCHMARK.json's metrics, so the
harness finds them by name exactly as it finds the real cells."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CELL = "tiny.train"


def make_root(root: Path, *, method: str = "adagradselect",
              residency: str = "banked", chips: int = 1) -> Path:
    """The tiny cell ``tiny.train``; on more than one chip, data-parallel
    over them with its banked store sharded over them (``zero1``)."""
    bench = root / "bench"
    for d in ("traffic", "workloads", "configs", "metrics"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "families", bench / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    cfg = json.loads((BENCH / "configs" / "qwen2.5-0.5b.json").read_text())
    cfg.update({"hidden_size": 64, "intermediate_size": 128,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "num_hidden_layers": 3, "vocab_size": 512,
                "torch_dtype": "float32"})
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" / "train.ags_banked.json").read_text())
    tr["job"].update({"method": method, "seq_len": 64, "global_batch": 4})
    # two of the five blocks a step, so that the step that exploits moves
    # one out of the bank that the next step brings back
    tr["job"]["select"]["k_percent"] = 40.0
    for k in ("prompt_len", "completion_len"):
        tr["records"][k].update({"median": 12, "min": 2, "max": 64})
    (bench / "traffic" / "tiny.json").write_text(json.dumps(tr))
    wl = json.loads((BENCH / "workloads"
                     / "qwen2.5-0.5b.train.ags_banked.json").read_text())
    # the tiny model runs at float32, where the program reads about 1e-7
    # (losses, gradients) and 2e-4 (changes) off the reference on the CPU
    offload = "host" if chips == 1 else "zero1"
    # a selection seed whose checked steps explore, exploit, explore and
    # whose first window step exploits, so a short window moves blocks too
    wl.update({"moment_residency": residency, "selection_seed": 14299,
               "offload": offload if residency == "banked" else "none",
               "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                          "change_gap": 1e-2}})
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(wl))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}]
    b["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tiny",
                       "chips": chips, "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    for m in b["per_layer"]:
        src = BENCH / "metrics" / f"{m['name']}.py"
        if src.exists():
            shutil.copy(src, bench / "metrics" / src.name)
    return root
