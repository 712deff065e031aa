import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
derive roofline terms — no device allocation (ShapeDtypeStruct inputs only).

The XLA_FLAGS assignment above MUST run before any other import (jax locks
the device count on first init); smoke tests and benches import repro
normally and see 1 device.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k \
      --mesh single --out results/
  python -m repro.launch.dryrun --all --mesh single --out results/
Mesh names: single = (16,16) ("data","model");  multi = (2,16,16)
("pod","data","model");  tiny = (2,4) (tests; set REPRO_DRYRUN_DEVICES=8).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ALL_ARCHS, get_config, get_shape, shapes_for  # noqa: E402
from repro.configs.base import OptimizerConfig, SelectConfig  # noqa: E402
from repro.distributed.sharding import batch_axes_of  # noqa: E402
from repro.launch import roofline as roofline_mod  # noqa: E402
from repro.launch import specs as specs_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh, make_mesh, mesh_config  # noqa: E402
from repro.models import registry  # noqa: E402


def lower_cell(cfg, shape, mesh, *, opt_offload="zero1", microbatch=0,
               moment_dtype="float32", train_method="adagradselect",
               extra_desc=""):
    """-> (lowered, compiled, meta) for one (arch, shape, mesh) cell.

    Production train defaults: ZeRO-1 moment sharding over the data axis
    (the TPU-native equivalent of the paper's 3.3 host offload — see
    core/offload.py) and microbatch gradient accumulation sized so the
    per-layer activation residency fits HBM. ``train_method`` picks the
    fine-tuning method from the repro.methods registry (selection family
    only — the SDS layout follows the masked-AdamW TrainState).
    """
    model = registry.get(cfg)
    baxes = batch_axes_of(mesh)
    batch_sds = specs_mod.data_batch_specs(cfg, shape, mesh)
    if microbatch == 0 and shape.kind == "train":
        microbatch = 8 if cfg.num_experts >= 64 else 4

    if shape.kind == "train":
        from repro import methods
        from repro.configs.base import TrainConfig
        sel_cfg = SelectConfig(k_percent=20.0)
        opt_cfg = OptimizerConfig(offload=opt_offload, microbatch=microbatch,
                                  moment_dtype=moment_dtype)
        method = methods.build(train_method, TrainConfig(
            model=cfg, select=sel_cfg, optimizer=opt_cfg))
        method_sel = getattr(method, "sel_cfg", None)
        if method_sel is None:
            raise ValueError(
                f"--train-method {train_method!r} is not a selection-family "
                f"method; the dry-run's TrainState SDS layout only covers "
                f"masked-AdamW methods (full/adagradselect/topk_grad/random/"
                f"lisa/grass)")
        state_sds = specs_mod.train_state_sds(
            cfg, mesh, opt_offload, moment_dtype, policy=method_sel.policy)
        fn = method.make_step(cfg, opt_cfg, mesh=mesh, batch_axes=baxes,
                              donate=True)
        with mesh:
            lowered = fn.lower(state_sds, batch_sds)
        meta_mem = _opt_memory(cfg, method, method_sel, state_sds)
    elif shape.kind == "prefill":
        p_sds, _ = specs_mod.params_sds(cfg, mesh)
        max_len = shape.seq_len

        def prefill(params, batch):
            return model.prefill(params, cfg, batch, max_len, mesh=mesh,
                                 batch_axes=baxes)

        with mesh:
            lowered = jax.jit(prefill).lower(p_sds, batch_sds)
    else:  # decode
        p_sds, _ = specs_mod.params_sds(cfg, mesh)
        gb, _ = specs_mod.batch_dims(cfg, shape)
        cache_sds = specs_mod.cache_specs(cfg, mesh, shape.global_batch,
                                          shape.seq_len)

        def serve_step(params, tokens, cache):
            return model.decode_step(params, cfg, tokens, cache, mesh=mesh,
                                     batch_axes=baxes)

        with mesh:
            lowered = jax.jit(serve_step, donate_argnums=(2,)).lower(
                p_sds, batch_sds["tokens"], cache_sds)

    t0 = time.time()
    compiled = lowered.compile()
    meta = {"compile_s": time.time() - t0}
    if shape.kind == "train":
        meta["opt_memory"] = meta_mem
    return lowered, compiled, meta


def _opt_memory(cfg, method, sel_cfg, state_sds) -> dict:
    """Optimizer-state memory for one train cell: the deterministic §3.3
    model (2 * P_sel * B) next to the *measured* column — jax.eval_shape
    accounting of the actual TrainState — plus the banked-residency
    projection (compact [k]-slot device banks, core/masked_adamw)."""
    from repro.core import masked_adamw, offload
    from repro.core.partition import build_partition
    from repro.utils.trees import tree_bytes

    partition = build_partition(cfg)
    rep = offload.optimizer_memory_report(
        partition, state_sds["params"], sel_cfg.k_percent,
        opt_state=state_sds["opt"])
    cap = method.slot_capacity(cfg)
    banked = jax.eval_shape(
        lambda p: masked_adamw.init_banked_opt_state(partition, p, cap,
                                                     store_policy=None),
        state_sds["params"])
    return {
        "model_full_bytes": rep.mem_full,
        "model_selective_bytes": rep.mem_selective,
        "model_pct_reduction": rep.pct_reduction,
        "measured_bytes": rep.mem_measured_device + rep.mem_measured_host,
        "banked_resident_bytes": tree_bytes(banked),
        # store<->bank traffic of one worst-case selection-change boundary
        # (full slot turnover: k admissions streamed in + k evictions
        # written back = 2 directions x m+v of the k largest blocks). This
        # is the per-interval transfer the async swap planner hides behind
        # compute; amortize over the policy's reselection interval for
        # bytes/step.
        "swap_bytes_per_interval": 2 * rep.mem_selective,
    }


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             opt_offload="zero1", microbatch=0, moment_dtype="float32",
             train_method="adagradselect", verbose=True, cfg_override=None,
             hlo_dir=None):
    cfg = cfg_override or get_config(arch)
    shape = get_shape(shape_name)
    if mesh_name == "multi":
        mesh = make_production_mesh(multi_pod=True)
    elif mesh_name == "single":
        mesh = make_production_mesh(multi_pod=False)
    else:
        mesh = make_mesh(mesh_config(mesh_name))
    n_dev = mesh.devices.size

    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "num_devices": int(n_dev), "kind": shape.kind,
              "opt_offload": opt_offload, "status": "ok"}
    try:
        lowered, compiled, meta = lower_cell(cfg, shape, mesh,
                                             opt_offload=opt_offload,
                                             microbatch=microbatch,
                                             moment_dtype=moment_dtype,
                                             train_method=train_method)
        ma = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        if hlo_dir:
            import gzip
            os.makedirs(hlo_dir, exist_ok=True)
            tag = f"{arch}_{shape_name}_{mesh_name}".replace("/", "_")
            with gzip.open(os.path.join(hlo_dir, f"{tag}.hlo.gz"), "wt") as f:
                f.write(hlo)
        mf = roofline_mod.model_flops(cfg, shape)
        rf = roofline_mod.analyze(cost, hlo, n_dev, model_flops_total=mf)
        result.update(meta)
        result["xla_cost_analysis"] = {  # undercounts scans; for reference
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        from repro.launch.hlo_cost import analyze_text as _at
        result["top_ops"] = _at(hlo, n_dev).summarize(8)
        result["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "host_argument_bytes": ma.host_argument_size_in_bytes,
            # live-buffer peak per device: args + temps (aliased outputs
            # reuse argument space)
            "peak_per_device": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        }
        result["roofline"] = rf.as_dict()
        if verbose:
            peak_gb = result["memory"]["peak_per_device"] / (1 << 30)
            print(f"[{arch} | {shape_name} | {mesh_name}] ok "
                  f"compile={meta['compile_s']:.1f}s peak={peak_gb:.2f}GiB/dev "
                  f"compute={rf.compute_s*1e3:.2f}ms memory={rf.memory_s*1e3:.2f}ms "
                  f"collective={rf.collective_s*1e3:.2f}ms -> {rf.bottleneck}")
            om = result.get("opt_memory")
            if om:
                gb = 1 << 30
                print(f"    opt-state: model 2PB={om['model_selective_bytes']/gb:.2f}GiB "
                      f"(full {om['model_full_bytes']/gb:.2f}GiB, "
                      f"-{om['model_pct_reduction']:.0f}%) "
                      f"measured={om['measured_bytes']/gb:.2f}GiB "
                      f"banked-resident={om['banked_resident_bytes']/gb:.2f}GiB "
                      f"swap/interval="
                      f"{om['swap_bytes_per_interval']/gb:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — report failures per-cell
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{arch} | {shape_name} | {mesh_name}] FAILED: "
                  f"{result['error']}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "tiny"])
    ap.add_argument("--offload", default="zero1",
                    choices=["none", "host", "zero1"])
    ap.add_argument("--microbatch", type=int, default=0,
                    help="0 = per-arch default (4; MoE 8)")
    ap.add_argument("--train-method", default="adagradselect",
                    help="fine-tuning method for train cells "
                         "(repro.methods registry, selection family)")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) cell")
    ap.add_argument("--out", default="results")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ALL_ARCHS[:10]:
            cfg = get_config(arch)
            for shape in shapes_for(cfg):
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape_name in cells:
        res = run_cell(arch, shape_name, args.mesh, opt_offload=args.offload,
                       microbatch=args.microbatch,
                       train_method=args.train_method,
                       hlo_dir=os.path.join(args.out, "hlo"))
        results.append(res)
        tag = f"{arch}_{shape_name}_{args.mesh}" + \
              (f"_{args.offload}" if args.offload != "zero1" else "")
        with open(os.path.join(args.out, f"dryrun_{tag}.json"), "w") as f:
            json.dump(res, f, indent=2)
    ok = sum(r["status"] == "ok" for r in results)
    print(f"\n{ok}/{len(results)} cells OK")
    return 0 if ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
