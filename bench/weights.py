"""Seeded random weights of a dense decoder, made on the device in one
jitted call, in the program's parameter tree (``program_tree``) or in the
published layout (``canonical``).

Both come from the same compiled draw, so the reference
(bench/reference/dense_decoder.py) and the program see the same numbers
without the reference taking anything the program made. The program pads
query heads (and possibly vocabulary rows) for sharding; the draw pads
them with zeros, which the program's head mask ignores, and the published
layout is its real part.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# leaf -> (shape from sizes, scale): normal(0, 1) * scale
def _leaf_specs(s: dict, bias: bool) -> dict:
    L, d, h, kvh, dh, f, v = (s["layers"], s["d"], s["heads"], s["kv_heads"],
                              s["head_dim"], s["ff"], s["vocab"])
    spec = {
        "embed": ((v, d), d ** -0.5),
        "final_norm": ((d,), None),
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, h, dh), d ** -0.5),
        "wk": ((L, d, kvh, dh), d ** -0.5),
        "wv": ((L, d, kvh, dh), d ** -0.5),
        "wo": ((L, h, dh, d), (h * dh) ** -0.5),
        "wg": ((L, d, f), d ** -0.5),
        "wu": ((L, d, f), d ** -0.5),
        "wd": ((L, f, d), f ** -0.5),
    }
    if bias:
        spec.update({"bq": ((L, h, dh), 0.1), "bk": ((L, kvh, dh), 0.1),
                     "bv": ((L, kvh, dh), 0.1)})
    return spec


def seed_key(seed: int):
    """A PRNG key from a seed of any size (SeedSequence takes it whole)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


# canonical leaf -> (path in the program's tree, axis the program pads and
# what to: "heads" / "vocab")
PROGRAM_PATH = {
    "embed": (("embed", "tok"), 0, "vocab"),
    "final_norm": (("final_norm", "scale"), None, None),
    "ln1": (("layers", "ln1", "scale"), None, None),
    "ln2": (("layers", "ln2", "scale"), None, None),
    "wq": (("layers", "attn", "wq"), 2, "heads"),
    "wk": (("layers", "attn", "wk"), None, None),
    "wv": (("layers", "attn", "wv"), None, None),
    "wo": (("layers", "attn", "wo"), 1, "heads"),
    "bq": (("layers", "attn", "bq"), 1, "heads"),
    "bk": (("layers", "attn", "bk"), None, None),
    "bv": (("layers", "attn", "bv"), None, None),
    "wg": (("layers", "mlp", "wg"), None, None),
    "wu": (("layers", "mlp", "wu"), None, None),
    "wd": (("layers", "mlp", "wd"), None, None),
}


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _draw(key, spec: tuple, dtype):
    # One compiled program makes every leaf, padded as the program lays it
    # out: XLA may round a draw differently in another computation, so the
    # program and the reference both take the bits of this one (the
    # reference slices the real heads out). Padding inside it makes each
    # leaf once, with no second copy beside the program's state.
    out = {}
    for i, (name, shape, scale, widths) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        # norm scales sit around 1, as trained ones do
        w = 1.0 + 0.1 * z if scale is None else z * scale
        w = w.astype(dtype)
        out[name] = jnp.pad(w, widths) if widths else w
    return out


def _widths(name: str, shape: tuple, padded_heads: int, padded_vocab: int):
    _, axis, what = PROGRAM_PATH[name]
    if axis is None:
        return None
    n = padded_heads if what == "heads" else padded_vocab
    if n == shape[axis]:
        return None
    widths = [(0, 0)] * len(shape)
    widths[axis] = (0, n - shape[axis])
    return tuple(widths)


def padded(seed: int, s: dict, bias: bool, padded_heads: int,
           padded_vocab: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf, zero-padded to the program's head and vocabulary
    padding, made on the device in one jitted call from the seed."""
    spec = tuple((name, shape, scale,
                  _widths(name, shape, padded_heads, padded_vocab))
                 for name, (shape, scale) in sorted(_leaf_specs(s, bias).items()))
    return _draw(seed_key(seed), spec=spec, dtype=jnp.dtype(dtype))


def canonical(seed: int, s: dict, bias: bool, padded_heads: int,
              padded_vocab: int, dtype=jnp.bfloat16) -> dict:
    """The weights in the published layout (real heads only): the real
    part of ``padded``."""
    w = padded(seed, s, bias, padded_heads, padded_vocab, dtype)
    real = {name: shape for name, (shape, _) in _leaf_specs(s, bias).items()}
    return {name: x[tuple(slice(0, n) for n in real[name])]
            for name, x in w.items()}


def at_path(tree: dict, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def program_tree(seed: int, s: dict, bias: bool, padded_heads: int,
                 padded_vocab: int, dtype=jnp.bfloat16) -> dict:
    """The weights in the program's parameter tree (``padded``'s arrays)."""
    tree: dict = {}
    for name, x in padded(seed, s, bias, padded_heads, padded_vocab,
                          dtype).items():
        path = PROGRAM_PATH[name][0]
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return tree


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def change_norms(params: dict, seed: int, s: dict, bias: bool,
                 padded_heads: int, padded_vocab: int) -> dict:
    """Per canonical leaf, the L2 norm of ``params - weights(seed)`` (the
    program's parameters after some steps, on the device or the host,
    against where they started), padding included: it started at zero."""
    dtype = jax.tree.leaves(params)[0].dtype
    w0 = padded(seed, s, bias, padded_heads, padded_vocab, dtype)
    return {name: float(_diff_norm(at_path(params, PROGRAM_PATH[name][0]),
                                   w0.pop(name)))
            for name in sorted(w0)}


def check_against(tree: dict, want) -> None:
    """Raise unless ``tree`` has the program's structure, shapes and dtypes
    (``want``: the program's own ``jax.eval_shape`` of its initializer)."""
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)
    exp = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    if got != exp:
        raise ValueError(f"bench weights do not match the program's "
                         f"parameter tree:\n{got}\nvs\n{exp}")
