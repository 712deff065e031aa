"""Training launcher.

CPU-scale (runs here):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-0.5b --smoke \
      --method adagradselect --k 20 --steps 200

Production (TPU pod; same code, mesh from --mesh):
  python -m repro.launch.train --arch qwen2.5-32b --mesh single \
      --steps 10000 --checkpoint-dir gs://.../ckpts

--method accepts any entry in the repro.methods registry (full,
adagradselect, topk_grad, random, lora, lisa, grass, ...).

Observability: ``--trace run.json`` exports a Perfetto-loadable Chrome
trace of the run, ``--metrics-json m.json`` dumps the metrics-registry
snapshot (inspect with ``python -m repro.launch.inspect m.json``), and
``--report`` prints the selection-frequency heatmap after training.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def parser() -> argparse.ArgumentParser:
    from repro import methods

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--method", default="adagradselect",
                    choices=sorted(methods.available()))
    ap.add_argument("--k", type=float, default=20.0, help="k%% blocks per step")
    ap.add_argument("--lora-rank", type=int, default=128)
    ap.add_argument("--lisa-interval", type=int, default=20,
                    help="lisa: steps between mask resamples")
    ap.add_argument("--grass-temperature", type=float, default=1.0,
                    help="grass: sampling ∝ cum_norms^T")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--data", default="synthetic_math",
                    choices=["synthetic_math", "jsonl", "jsonl_sft"],
                    help="synthetic_math/jsonl: legacy pure-f(step) "
                         "sources; jsonl_sft: streaming pipeline over "
                         "{'prompt','completion'} lines (cursor "
                         "checkpointed, packed under --pack)")
    ap.add_argument("--data-path", default="",
                    help="corpus path for --data jsonl / jsonl_sft")
    ap.add_argument("--pack", action="store_true",
                    help="segment-aware sequence packing (jsonl_sft, or "
                         "synthetic_math via its record form): multiple "
                         "examples per row with block-diagonal attention "
                         "+ per-segment positions")
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help=">0: async prefetcher builds and device_puts this "
                         "many batches ahead of the train loop "
                         "(bit-identical trajectory, prefetch on or off)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--offload", default="none", choices=["none", "host", "zero1"])
    ap.add_argument("--moment-residency", default="device",
                    choices=["device", "banked"],
                    help="banked: compact [k]-slot device moment banks over "
                         "a full store placed per --offload (paper 3.3); "
                         "banked + --offload zero1 shards the store 1/dp "
                         "over the mesh's data axis and requires --mesh")
    ap.add_argument("--async-swap", default="on", choices=["on", "off"],
                    help="banked only: 'on' overlaps the selection-change "
                         "boundary with compute (a background thread "
                         "prefetches the policy's predicted next admit set "
                         "and writes predicted evictions back while phase B "
                         "runs; mispredictions fall back to the synchronous "
                         "swap — the trajectory is bit-identical either "
                         "way); 'off' forces every boundary synchronous")
    ap.add_argument("--mesh", default=None,
                    choices=[None, "single", "multi", "tiny", "data"],
                    help="run data-parallel (or DP x TP) on a device mesh: "
                         "batch shards over the data axes, params/moments "
                         "follow distributed/sharding.py (TP where the "
                         "model axis is >1, ZeRO-1 moments under --offload "
                         "zero1). 'single'=(16,16) 'multi'=(2,16,16) "
                         "'tiny'=(2,4); 'data'=(N,1) over every visible "
                         "device — the CPU-testable topology "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--trace", default="",
                    help="export a Chrome trace-event JSON (load in "
                         "ui.perfetto.dev) of the run: train_step spans, "
                         "banked phase_a/swap/phase_b, the background "
                         "swap-dispatch thread on its own track. Enables "
                         "the obs layer (adds host syncs; trajectories "
                         "stay bit-identical)")
    ap.add_argument("--metrics-json", default="",
                    help="write the full obs registry snapshot (counters/"
                         "gauges/histogram summaries + selection "
                         "telemetry) to this path; feed it to "
                         "repro.launch.inspect")
    ap.add_argument("--report", action="store_true",
                    help="print the selection-frequency heatmap "
                         "(exploration->exploitation view) after training")
    return ap


def train_config(args: argparse.Namespace):
    """The ``TrainConfig`` the command line describes."""
    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import OptimizerConfig, SelectConfig, TrainConfig

    mcfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainConfig(
        model=mcfg,
        method=args.method,
        select=SelectConfig(k_percent=args.k,
                            steps_per_epoch=max(1, args.steps // 4),
                            lisa_interval=args.lisa_interval,
                            grass_temperature=args.grass_temperature),
        optimizer=OptimizerConfig(lr=args.lr, total_steps=args.steps,
                                  offload=args.offload,
                                  moment_residency=args.moment_residency,
                                  async_swap=args.async_swap == "on",
                                  lora_rank=args.lora_rank),
        seq_len=args.seq_len, global_batch=args.global_batch,
        steps=args.steps, seed=args.seed,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)
    return tcfg


def build_trainer(args: argparse.Namespace):
    """The ``Trainer`` that ``main`` runs for parsed ``args`` (config, mesh
    and data source as the command line says)."""
    tcfg = train_config(args)
    mesh = None
    batch_axes = ("data",)
    if args.mesh:
        from repro.launch.mesh import (make_data_mesh, make_mesh,
                                       make_production_mesh, mesh_config)
        if args.mesh == "data":
            mesh = make_data_mesh()
        elif args.mesh == "tiny":
            mesh = make_mesh(mesh_config("tiny"))
        else:
            mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        batch_axes = tuple(a for a in mesh.axis_names if a != "model")

    data_source = None
    if args.pack and args.data == "jsonl":
        raise SystemExit("--pack needs example boundaries; use --data "
                         "jsonl_sft ({'prompt','completion'} lines) — "
                         "plain jsonl documents are ring-packed already")
    if args.data != "synthetic_math" or args.pack:
        from repro.data import loader
        kind = args.data
        if args.data == "synthetic_math" and args.pack:
            kind = "packed_math"  # synthetic corpus as packable records
        data_source = loader.make_source(
            kind, seq_len=args.seq_len, global_batch=args.global_batch,
            seed=args.seed, path=args.data_path, pack=args.pack)

    from repro.train.trainer import Trainer
    return Trainer(tcfg, mesh=mesh, batch_axes=batch_axes,
                   data_source=data_source,
                   prefetch_depth=args.prefetch_depth)


def main(argv=None):
    args = parser().parse_args(argv)

    from repro import obs
    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    obs_on = bool(args.trace or args.metrics_json or args.report)
    if obs_on:
        obs.enable()

    trainer = build_trainer(args)
    mcfg = trainer.tcfg.model
    report = trainer.method.trainable_param_report(mcfg, trainer.state)
    resident = (f", resident {report.opt_bytes_resident / (1 << 20):.1f} MiB"
                if report.opt_bytes_resident >= 0 else "")
    print(f"[{args.method}] trainable {report.num_params_trainable:,}/"
          f"{report.num_params_total:,} params "
          f"({report.trainable_fraction:.1%}), "
          f"opt-state {report.opt_bytes / (1 << 20):.1f} MiB (model)"
          f"{resident}  {report.detail}")
    start = trainer.maybe_restore()
    if start:
        print(f"resumed from step {start}")
    log = trainer.train()
    print(f"final loss: {log.losses[-1]:.4f}  "
          f"mean step time: {np.mean(log.step_times[3:]):.3f}s")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"losses": log.losses, "step_times": log.step_times,
                       "metrics": log.metrics}, f)
    if args.trace:
        obs.export_trace(args.trace)
        print(f"trace written to {args.trace} (open in ui.perfetto.dev)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(obs.snapshot(), f, indent=2)
        print(f"metrics snapshot written to {args.metrics_json}")
    if args.report:
        from repro.obs import report as obs_report
        print(obs_report.render_selection_trace(obs.selection_trace()))
    if obs_on:
        obs.disable()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
