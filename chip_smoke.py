#!/usr/bin/env python3
"""Smoke run of the system's main paths on TPU, through the normal entry
points, at the full width of qwen2.5-0.5b with seeded random weights.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips, data-parallel only

One chip, in order (any failure exits nonzero; no exception is caught):
  1. device   - JAX's first device must be a TPU; there is no CPU fallback.
  2. kernels  - block grad-norm, masked AdamW, banked masked AdamW and paged
                decode attention, each once at qwen2.5-0.5b widths against
                the jnp oracles in kernels/ref.py; the AdamW kernels are
                held to the oracle's update p' - p on f32 parameters.
  3. train    - the training launcher's Trainer: AdaGradSelect, k 20%,
                global batch 8 x 1024 tokens of synthetic math, 6 steps,
                seed 0; (a) dense device moments, (b) banked moments over
                the host-RAM store with the async swap planner, (c) (a)
                with the kernels off (use_pallas="never", the XLA path).
                Losses must be finite and (b) and (c) must follow (a).
  4. serve    - a paged and a dense ServeEngine over (a)'s parameters, four
                synthetic-math prompts of different lengths, 32 greedy
                tokens each; every request completes and the engines agree.

--four-chips runs only the data-parallel trainer (make_data_mesh over four
chips, banked moments with the ZeRO-1 sharded store, batch 8, 6 steps) and
the same run on the first chip alone: losses must agree and each chip must
hold a quarter of the store.

Wherever use_pallas="auto" picks the kernels (on TPU it must), every
training step program is checked to carry them.

The last line of standard output is one JSON object with the device as JAX
reports it. Every time printed is a smoke-run wall time, not a benchmark
number.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-0.5b"
STEPS, SEQ_LEN, GLOBAL_BATCH, K_PERCENT, SEED = 6, 1024, 8, 20, 0
DP = 4  # chips of the --four-chips data mesh
NEW_TOKENS = 32
PROMPT_DIGITS = (2, 3, 5, 8)  # synthetic-math prompts of 7, 9, 13, 19 tokens

# Tolerances. The grad-norm and attention bounds are the ones
# tests/test_kernels.py holds the interpreted kernels to. The AdamW kernels
# run on f32 parameters and are held to the oracle's update p' - p (about
# lr = 1e-2 in size, its weight-decay term about 1e-4): the bound leaves
# room for a few f32 ulps of the update and of p (|p| about 6 at most), no
# more. The dense and banked steps compile phase A on its own or fused with
# the update, so XLA may order reductions differently; on the CPU the two
# are bit-exact. The kernels-off run rounds the same f32 update to bf16
# parameters through XLA, and the four-chip run reduces gradients across
# chips, which reorders sums the same way. Greedy paged and dense decoding
# may part only at a near tie: where they do, the two tokens' logits under
# the plain XLA forward must lie within LOGIT_GAP_TOL.
SQ_NORM_RTOL = 1e-3
ADAMW_TOL = {"p'-p": dict(rtol=1e-4, atol=2e-6),
             "m'": dict(rtol=1e-4, atol=1e-6),
             "v'": dict(rtol=1e-4, atol=1e-8)}
ATTN_TOL = dict(rtol=2e-2, atol=2e-2)
LOSS_RTOL = 2e-3
LOGIT_GAP_TOL = 0.05


def say(msg: str) -> None:
    print(msg, flush=True)


def device_check(need: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX's first device is {d.platform!r})")
    if len(devs) < need:
        sys.exit(f"chip_smoke: needs {need} TPU chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _close(name, got, want, tol):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    say(f"  {name}: max |kernel - ref| = {err:.3e}  (tolerance {tol})")
    np.testing.assert_allclose(got, want, **tol, err_msg=name)


def _adamw_close(kernel, got, want, p_rows) -> None:
    """(p', m', v') from ``kernel`` against the oracle's [rows, R] triple;
    p' is compared as the update p' - p."""
    (p2, m2, v2), (wp, wm, wv) = got, want
    flat = lambda t: t.reshape(t.shape[0], -1)  # noqa: E731
    for name, a, b in (("p'-p", flat(p2) - p_rows, wp - p_rows),
                       ("m'", flat(m2), wm), ("v'", flat(v2), wv)):
        _close(f"{kernel} {name}", a, b, ADAMW_TOL[name])


def kernels_phase(cfg) -> None:
    """Each main-path kernel once at the model's widths against ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.models.layers.attention import _hmap

    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    nl = cfg.num_layers
    shape = (nl, cfg.d_model, cfg.d_ff)  # the MLP weight leaf
    p = jax.random.normal(ks[0], shape)  # f32, so p' holds the whole update
    g = (0.1 * jax.random.normal(ks[1], shape)).astype(jnp.bfloat16)
    m = 0.01 * jax.random.normal(ks[2], shape)
    v = 0.001 * jnp.abs(jax.random.normal(ks[3], shape))
    flat = lambda t: t.reshape(t.shape[0], -1)  # noqa: E731
    hp = (1e-2, 0.9, 0.999, 1e-8, 0.01)

    got = jax.jit(ops.block_grad_sq_norms)(g)
    _close("block_grad_sq_norms", got, ref.block_grad_sq_norms(g),
           dict(rtol=SQ_NORM_RTOL))

    sel = jnp.asarray(np.arange(nl) % 2, jnp.float32)
    cnt = jnp.arange(1, nl + 1, dtype=jnp.float32)
    adamw = jax.jit(lambda *a: ops.masked_adamw(*a, *hp[1:]))
    got = adamw(p, g, m, v, sel, cnt, hp[0])
    want = ref.masked_adamw(flat(p), flat(g), flat(m), flat(v), sel, cnt, *hp)
    _adamw_close("masked_adamw", got, want, flat(p))

    # bank rows -> leaf rows; nl marks an empty slot
    slots = jnp.asarray([3 % nl, 10 % nl, nl, 17 % nl, 0], jnp.int32)
    cap = slots.shape[0]
    bsel = jnp.asarray([1, 1, 0, 0, 1], jnp.float32)
    bcnt = jnp.asarray([2, 1, 1, 5, 3], jnp.float32)
    mb = 0.01 * jax.random.normal(ks[7], (cap,) + shape[1:])
    vb = 0.001 * jnp.abs(jax.random.normal(ks[6], (cap,) + shape[1:]))
    banked = jax.jit(lambda *a: ops.banked_masked_adamw(*a, *hp[1:]))
    got = banked(p, g, mb, vb, slots, bsel, bcnt, hp[0])
    rows = jnp.minimum(slots, nl - 1)
    want = ref.masked_adamw(flat(p[rows]), flat(g[rows]), flat(mb), flat(vb),
                            bsel, bcnt, *hp)
    _adamw_close("banked_masked_adamw", got, want, flat(p[rows]))

    b, page_size, max_len = 4, 16, 512
    maxp = max_len // page_size
    num_pages = b * maxp
    h, kvh, d = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim
    hmap = jnp.asarray(_hmap(cfg), jnp.int32)
    pool = (num_pages, kvh, page_size, d)
    k_pool = (0.5 * jax.random.normal(ks[4], pool)).astype(jnp.bfloat16)
    v_pool = (0.5 * jax.random.normal(ks[5], pool)).astype(jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(SEED).permutation(num_pages)
                         .reshape(b, maxp), jnp.int32)
    vlen = jnp.asarray([1, 100, 257, max_len], jnp.int32)
    q = jax.random.normal(ks[3], (b, 1, h, d)).astype(jnp.bfloat16)
    got = jax.jit(ops.paged_decode_attention)(q, k_pool, v_pool, tables,
                                              vlen, hmap)
    want = ref.paged_decode_attention(q.reshape(b, h, d), k_pool, v_pool,
                                      tables, vlen, hmap)
    _close("paged_decode_attention", got.reshape(b, h, d), want, ATTN_TOL)


def _launcher_args(extra: list[str], *, smoke: bool, seq_len: int):
    from repro.launch import train as launcher
    argv = ["--arch", ARCH, "--method", "adagradselect", "--k",
            str(K_PERCENT), "--steps", str(STEPS), "--seq-len", str(seq_len),
            "--global-batch", str(GLOBAL_BATCH), "--seed", str(SEED)]
    return launcher.parser().parse_args(argv + (["--smoke"] if smoke else [])
                                        + extra)


def _check_kernels_in_step(trainer, batch) -> None:
    """The lowered step programs must carry the Pallas kernels."""
    fn = trainer.step_fn
    if hasattr(fn, "forward_select"):  # banked: two compiled phases
        state = trainer.state
        text = fn.forward_select.lower(state["params"], state["sel"],
                                       batch).as_text()
        grads = state["params"]
        text += fn.apply.lower(state["params"], grads, state["opt"]["banks"],
                               state["opt"]["counts"],
                               state["sel"]["mask"], state["step"]).as_text()
        want = ("block_grad_sq_norms", "banked_masked_adamw")
    else:
        text = fn.lower(trainer.state, batch).as_text()
        want = ("block_grad_sq_norms", "masked_adamw")
    missing = [k for k in want if k not in text]
    if "tpu_custom_call" not in text or missing:
        raise AssertionError(f"training step runs without kernels {missing}")
    say(f"  step program carries Pallas kernels: {', '.join(want)}")


def _release(trainer) -> None:
    """Stop the trainer's swap-planner thread and free its device state
    before the next phase places its own."""
    planner = getattr(trainer.step_fn, "swap_planner", None)
    if planner is not None:
        planner.close()
    trainer.state = None
    gc.collect()


def _run_trainer(name, trainer):
    import jax
    import numpy as np

    from repro.kernels import ops

    t0 = time.perf_counter()
    log = trainer.train()
    wall = time.perf_counter() - t0
    losses = np.asarray(log.losses, np.float64)
    say(f"  [{name}] losses: {[float(x) for x in losses]}")
    say(f"  [{name}] wall {wall:.1f}s for {len(losses)} steps incl. compile;"
        f" mean per step after step 0: {np.mean(log.step_times[1:]):.4f}s "
        f"(smoke-run wall time)")
    if not np.all(np.isfinite(losses)) or len(losses) != STEPS:
        raise AssertionError(f"[{name}] losses not finite: {losses}")
    if ops.use_kernels(trainer.tcfg.model):
        batch = jax.tree.map(jax.numpy.asarray, trainer.data.batch_at(0))
        _check_kernels_in_step(trainer, batch)
    return losses


def _same_losses(a, b, what: str) -> None:
    import numpy as np
    gap = float(np.max(np.abs(a - b) / np.maximum(1.0, np.abs(a))))
    say(f"  {what}: max relative loss gap {gap:.3e} (tolerance {LOSS_RTOL})")
    if gap > LOSS_RTOL:
        raise AssertionError(f"{what}: losses part by {gap:.3e}")


def train_phase(*, smoke: bool = False, seq_len: int = SEQ_LEN):
    """(a) dense moments, (b) banked moments over the host store, then (c)
    (a) with the kernels off. Returns (a)'s parameters and model config."""
    from repro.launch import train as launcher
    from repro.train.trainer import Trainer

    dense_args = _launcher_args(["--moment-residency", "device"],
                                smoke=smoke, seq_len=seq_len)
    tr = launcher.build_trainer(dense_args)
    dense = _run_trainer("a: dense moments", tr)
    params, cfg = tr.state["params"], tr.tcfg.model
    _release(tr)  # free the dense moments before (b) places its own state

    tr = launcher.build_trainer(_launcher_args(
        ["--moment-residency", "banked", "--offload", "host",
         "--async-swap", "on"], smoke=smoke, seq_len=seq_len))
    banked = _run_trainer("b: banked moments, host store", tr)
    stats = tr.step_fn.swap_stats
    say(f"  [b] swap planner: {stats.dispatches} dispatches, predicted hit "
        f"rate {stats.predicted_hit_rate:.2f}")
    _release(tr)

    tcfg = launcher.train_config(dense_args)
    tr = Trainer(dataclasses.replace(
        tcfg, model=tcfg.model.replace(use_pallas="never")),
        prefetch_depth=dense_args.prefetch_depth)
    xla = _run_trainer("c: dense moments, kernels off", tr)
    _release(tr)
    _same_losses(dense, banked, "banked vs dense")
    _same_losses(dense, xla, "dense: kernels vs kernels off")
    return params, cfg


def _prompts(cfg):
    from repro.data import synthetic
    out = []
    for i, digits in enumerate(PROMPT_DIGITS):
        task = synthetic.MathTaskConfig(digits=digits, seq_len=128, seed=SEED)
        toks, _ = synthetic.sample_problem(task, task.eval_offset + i)
        out.append(toks[:synthetic.prompt_len(task)])
    return out


def _logit_gap(cfg, params, context, tok_a, tok_b) -> float:
    """|logit(tok_a) - logit(tok_b)| after ``context`` under the plain XLA
    forward (no kernels, full sequence)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import registry
    toks = jnp.asarray(np.asarray(context, np.int32)[None])
    logits, _, _ = registry.get(cfg).apply_train(
        params, cfg.replace(use_pallas="never"),
        {"tokens": toks, "loss_mask": jnp.ones(toks.shape, jnp.float32)})
    last = np.asarray(logits[0, -1], np.float32)
    return abs(float(last[tok_a]) - float(last[tok_b]))


def serve_phase(cfg, params) -> None:
    """Paged and dense engines over the same params and prompts."""
    import numpy as np

    from repro.kernels import ops
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request

    prompts = _prompts(cfg)
    say(f"  prompt lengths: {[len(p) for p in prompts]}; paged decode "
        f"kernel on: {ops.use_kernels(cfg)}")
    out = {}
    for layout in ("paged", "dense"):
        eng = ServeEngine(cfg, params, ServeConfig(
            max_len=128, num_slots=len(prompts), kv_layout=layout))
        t0 = time.perf_counter()
        res = eng.run([Request(uid=i, tokens=p, max_new_tokens=NEW_TOKENS)
                       for i, p in enumerate(prompts)])
        wall = time.perf_counter() - t0
        eng.close()
        comps = res.completions
        if sorted(comps) != list(range(len(prompts))):
            raise AssertionError(f"[{layout}] requests missing: {sorted(comps)}")
        for uid, c in comps.items():
            if c.finish_reason != "length" or len(c.tokens) != NEW_TOKENS:
                raise AssertionError(f"[{layout}] request {uid} ended "
                                     f"{c.finish_reason} after "
                                     f"{len(c.tokens)} tokens")
        say(f"  [{layout}] {len(comps)} requests x {NEW_TOKENS} tokens in "
            f"{wall:.1f}s incl. compile (smoke-run wall time)")
        out[layout] = {uid: np.asarray(c.tokens) for uid, c in comps.items()}

    for uid, prompt in enumerate(prompts):
        a, b = out["dense"][uid], out["paged"][uid]
        diff = np.nonzero(a != b)[0]
        if not len(diff):
            say(f"  request {uid}: paged == dense ({NEW_TOKENS} tokens)")
            continue
        i = int(diff[0])
        gap = _logit_gap(cfg, params, np.concatenate([prompt, a[:i]]),
                         int(a[i]), int(b[i]))
        say(f"  request {uid}: first divergence at token {i} "
            f"(dense {a[i]}, paged {b[i]}), logit gap {gap:.4f} "
            f"(tolerance {LOGIT_GAP_TOL})")
        if gap > LOGIT_GAP_TOL:
            raise AssertionError(f"request {uid}: paged and dense decoding "
                                 f"part at token {i} by logit gap {gap:.4f}")


def four_chip_phase(*, smoke: bool = False, seq_len: int = SEQ_LEN) -> None:
    """DP-way banked + ZeRO-1 training against the same run on one chip."""
    from repro.core import offload
    from repro.launch import train as launcher
    from repro.launch.mesh import make_data_mesh
    from repro.train.trainer import Trainer

    args = _launcher_args(["--moment-residency", "banked", "--offload",
                           "zero1", "--mesh", "data"], smoke=smoke,
                          seq_len=seq_len)
    store = {}
    runs = {}
    for n in (1, DP):
        tr = (launcher.build_trainer(args) if n == DP else
              Trainer(launcher.train_config(args), mesh=make_data_mesh(1)))
        if tr.mesh.devices.size != n:
            raise AssertionError(f"mesh holds {tr.mesh.devices.size} chips, "
                                 f"expected {n}")
        store[n] = offload.resident_opt_bytes(
            tr.state["opt"]["store"])["device_per_device"]
        runs[n] = _run_trainer(f"dp={n}: banked, zero1 store", tr)
        _release(tr)
    _same_losses(runs[1], runs[DP], f"dp={DP} vs one chip")
    ratio = store[DP] / store[1]
    say(f"  store bytes per chip: dp={DP} {store[DP]}, one chip {store[1]}, "
        f"ratio {ratio:.4f}")
    if abs(ratio - 1 / DP) > 1e-9:
        raise AssertionError(f"store per chip is {ratio:.4f} of the "
                             f"replicated layout, expected 1/{DP}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help=f"run only the dp={DP} banked + ZeRO-1 trainer and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = device_check(DP if args.four_chips else 1)
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import place_compile_cache
    if not ops.use_kernels(get_config(ARCH)):
        sys.exit("chip_smoke: use_pallas='auto' leaves the kernels off on TPU")
    say(f"[cache] {place_compile_cache()}")

    def phase(name, fn, *a, **kw):
        say(f"[{name}]")
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        say(f"[{name}] passed in {time.perf_counter() - t0:.1f}s")
        return out

    if args.four_chips:
        phase("four-chips", four_chip_phase)
    else:
        phase("kernels", kernels_phase, get_config(ARCH))
        params, cfg = phase("train", train_phase)
        phase("serve", serve_phase, cfg, params)
    say(f"[done] {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
