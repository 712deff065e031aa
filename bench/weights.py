"""Seeded random weights, made on the device in one jitted call from a list
of leaves that a family module gives (``bench/families/``): each leaf's
published shape, its scale, the path it takes in the program's parameter
tree, and the axis the program pads.

The program and the reference take the bits of the same compiled draw,
so the reference sees the same numbers without taking anything the program
made. The program pads some axes (query heads, vocabulary rows) for
sharding; the draw pads them with zeros, which the program masks, and the
published layout is the real part.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Leaf:
    """One published leaf: ``normal(0, 1) * scale`` (a norm's scale,
    ``scale`` None, sits around 1), at ``path`` in the program's tree,
    whose axis ``pad_axis`` the program pads to ``pad_to`` entries."""
    name: str
    shape: tuple
    scale: float | None
    path: tuple
    pad_axis: int | None = None
    pad_to: int | None = None

    def widths(self):
        if self.pad_axis is None or self.pad_to == self.shape[self.pad_axis]:
            return None
        w = [(0, 0)] * len(self.shape)
        w[self.pad_axis] = (0, self.pad_to - self.shape[self.pad_axis])
        return tuple(w)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (SeedSequence takes it whole)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _draw(key, spec: tuple, dtype):
    # One compiled program makes every leaf, padded as the program lays it
    # out: XLA may round a draw differently in another computation, so the
    # program and the reference both take the bits of this one (the
    # reference slices the real part out). Padding inside it makes each
    # leaf once, with no second copy beside the program's state.
    out = {}
    for i, (name, shape, scale, widths) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = 1.0 + 0.1 * z if scale is None else z * scale
        w = w.astype(dtype)
        out[name] = jnp.pad(w, widths) if widths else w
    return out


def padded(seed: int, leaves: list, dtype=jnp.bfloat16,
           shardings: dict | None = None) -> dict:
    """Every leaf by name, zero-padded as the program pads it, made on the
    device in one jitted call from the seed; ``shardings`` (by name) places
    them as the program's state does on a mesh."""
    spec = tuple((lf.name, lf.shape, lf.scale, lf.widths())
                 for lf in sorted(leaves, key=lambda lf: lf.name))
    if shardings is None:
        return _draw(seed_key(seed), spec=spec, dtype=jnp.dtype(dtype))
    draw = jax.jit(functools.partial(_draw.__wrapped__, spec=spec,
                                     dtype=jnp.dtype(dtype)),
                   out_shardings=shardings)
    return draw(seed_key(seed))


def canonical(seed: int, leaves: list, dtype=jnp.bfloat16) -> dict:
    """The weights in the published layout: the real part of ``padded``."""
    w = padded(seed, leaves, dtype)
    return {lf.name: w[lf.name][tuple(slice(0, n) for n in lf.shape)]
            for lf in leaves}


def at_path(tree: dict, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def program_tree(seed: int, leaves: list, dtype=jnp.bfloat16,
                 shardings: dict | None = None) -> dict:
    """The weights in the program's parameter tree (``padded``'s arrays);
    ``shardings``: the program's parameter shardings, a tree like it."""
    by_name = None if shardings is None else {
        lf.name: at_path(shardings, lf.path) for lf in leaves}
    tree: dict = {}
    arrays = padded(seed, leaves, dtype, by_name)
    for lf in leaves:
        node = tree
        for k in lf.path[:-1]:
            node = node.setdefault(k, {})
        node[lf.path[-1]] = arrays[lf.name]
    return tree


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def change_norms(params: dict, seed: int, leaves: list) -> dict:
    """Per published leaf, the L2 norm of ``params - weights(seed)`` (the
    program's parameters after some steps, on the device or the host,
    against where they started), padding included: it started at zero."""
    dtype = jax.tree.leaves(params)[0].dtype
    w0 = padded(seed, leaves, dtype)
    path = {lf.name: lf.path for lf in leaves}
    return {name: float(_diff_norm(at_path(params, path[name]),
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
