"""A new model family enters the benchmark by files alone: a family module,
a configuration that names it, a workload file and BENCHMARK.json entries.
The family here is a throwaway dense decoder with an untied output head,
a block of its own: blocks of three sizes in a list the harness has never
seen."""
import json

import jax

import harness
import run
import tiny

CELL = "untied.train"

FAMILY = '''"""A dense decoder with an untied output head."""
from pathlib import Path

import jax
import jax.numpy as jnp

import harness
import program
import weights
from reference import adagradselect, dense_decoder as ref

DENSE = harness.load_module(Path(__file__).with_name("dense_decoder.py"))
sizes = DENSE.sizes
train_flops = DENSE.train_flops          # the head is the D*V matmul


def _tied(config):
    return {**config, "tie_word_embeddings": True}


def program_config(config):
    return program.model_config(config, DENSE.FIELDS,
                                head_dim=sizes(config)["head_dim"])


def leaves(config):
    s = sizes(config)
    vocab = program_config(config).padded_vocab_size
    return DENSE.leaves(_tied(config)) + [weights.Leaf(
        "lm_head", (s["d"], s["vocab"]), s["d"] ** -0.5, ("lm_head", "w"),
        1, vocab)]


def block_params(config):
    s = sizes(config)
    return DENSE.block_params(config) + [s["d"] * s["vocab"]]


def groups(config):
    s = sizes(config)
    return DENSE.groups(config) + [
        adagradselect.Group(s["layers"] + 2, 1, False, ("lm_head",))]


def reference_train(config, seed, batches, *, opt, sel, method,
                    program_seed, mode="f32"):
    dtype = jnp.dtype(config["torch_dtype"])
    w0 = weights.canonical(seed, leaves(config), dtype)
    c = ref.consts(config)

    def row_loss(w, tokens, loss_mask, seg, pos):
        h = ref.hidden(c, w, tokens, seg, pos, mode)
        z = ref._mm("sd,dv->sv", h, w["lm_head"], mode)[:-1]
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, tokens[1:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * loss_mask[1:])

    return adagradselect.reference_train(
        row_loss, w0, batches, groups(config), opt=opt, sel=sel,
        method=method, program_seed=program_seed, param_dtype=dtype)
'''


def _add_family(root):
    bench = root / "bench"
    (bench / "families" / "untied_decoder.py").write_text(FAMILY)
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg.update({"tie_word_embeddings": False,
                "reference": "bench/families/untied_decoder.py"})
    (bench / "configs" / "untied.json").write_text(json.dumps(cfg))
    (bench / "workloads" / f"{CELL}.json").write_text(
        (bench / "workloads" / f"{tiny.CELL}.json").read_text())
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "untied", "source": "test", "reduced": [],
                         "file": "bench/configs/untied.json", "why": "test"})
    b["workloads"].append({"name": CELL, "config": "untied",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def test_a_new_family_is_found_by_adding_files(tmp_path, capsys):
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    _add_family(root)
    changed = [p for p, data in before.items() if p.read_bytes() != data]
    assert changed == [root / "BENCHMARK.json"]
    cell = harness.load_cell(CELL, root)
    sizes = sorted(set(cell.family.block_params(cell.config)))
    assert len(cell.family.block_params(cell.config)) == 3 + 3
    assert len(sizes) == 3          # norm, layer, embedding and head
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 9),
                     "--seconds", "0.3", "--trace", "0"], root=root,
                    devices=jax.devices()) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["blocks_restored"]["value"] >= 1
    compared = next(ln for ln in err.splitlines()
                    if ln.startswith("[check] leaves compared"))
    assert "'lm_head'" in compared
