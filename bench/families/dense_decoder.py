"""The dense decoder family (Qwen2.5-0.5B): everything the harness needs to
know of an architecture, found through the configuration file's
``"reference"`` key. A new family is a module like this one beside it,
with the same names:

* ``sizes(config)``: the published sizes the rest reads;
* ``program_config(config)``: the program's ``ModelConfig`` that runs it;
* ``leaves(config)``: the seeded draw's leaves (``bench/weights.Leaf``),
  each with its path in the program's tree and the axis the program pads;
  the harness draws them in the program's layout and in the published one,
  and takes each leaf's first gradient and change by them;
* ``train_flops(config, tokens, pairs)``: forward plus backward FLOPs;
* ``block_params(config)``: published parameters of each block the method
  selects among, in the program's block order;
* ``reference_train(config, seed, batches, **job)``: the plain reference's
  first steps of the job.

A dense decoder of ``L`` layers, hidden size ``D``, ``H`` query heads and
``KVH`` key/value heads of size ``Dh``, feed-forward width ``F`` and
vocabulary ``V`` with tied embeddings:

* matmul parameters per token: per layer ``D*H*Dh + 2*D*KVH*Dh + H*Dh*D +
  3*D*F``, plus ``D*V`` for the output head. The embedding lookup is not a
  matmul. Published heads only: heads the program pads for sharding do no
  work that anyone needs.
* attention: a query at position ``i`` of a segment attends to ``i + 1``
  keys (causal within its packed segment), which costs ``2*Dh`` FLOPs for
  the score and ``2*Dh`` for the value mix per head.
* training FLOPs are 3x the forward (forward, and backward for inputs and
  weights); recomputation does not count.
* blocks: the embedding, each layer, the final norm.
"""
from __future__ import annotations

import program
import weights
from reference import adagradselect, dense_decoder as ref

# published key -> ModelConfig field
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "hidden_act": "act",
    "attention_bias": "attn_bias",
    "partial_rotary_factor": "partial_rotary_factor",
    "torch_dtype": "dtype",
}


def sizes(config: dict) -> dict:
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {"layers": int(config["num_hidden_layers"]), "d": d, "heads": h,
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or d // h),
            "ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "bias": bool(config.get("attention_bias", False))}


def program_config(config: dict):
    if not config.get("tie_word_embeddings", False):
        raise ValueError("bench: the dense decoder family ties embeddings")
    return program.model_config(config, FIELDS, head_dim=sizes(config)[
        "head_dim"])


def leaves(config: dict) -> list:
    s = sizes(config)
    L, d, h, kvh, dh, f, v = (s["layers"], s["d"], s["heads"], s["kv_heads"],
                              s["head_dim"], s["ff"], s["vocab"])
    mcfg = program_config(config)
    heads, vocab = mcfg.padded_heads, mcfg.padded_vocab_size
    Leaf = weights.Leaf
    out = [
        Leaf("embed", (v, d), d ** -0.5, ("embed", "tok"), 0, vocab),
        Leaf("final_norm", (d,), None, ("final_norm", "scale")),
        Leaf("ln1", (L, d), None, ("layers", "ln1", "scale")),
        Leaf("ln2", (L, d), None, ("layers", "ln2", "scale")),
        Leaf("wq", (L, d, h, dh), d ** -0.5, ("layers", "attn", "wq"), 2,
             heads),
        Leaf("wk", (L, d, kvh, dh), d ** -0.5, ("layers", "attn", "wk")),
        Leaf("wv", (L, d, kvh, dh), d ** -0.5, ("layers", "attn", "wv")),
        Leaf("wo", (L, h, dh, d), (h * dh) ** -0.5, ("layers", "attn", "wo"),
             1, heads),
        Leaf("wg", (L, d, f), d ** -0.5, ("layers", "mlp", "wg")),
        Leaf("wu", (L, d, f), d ** -0.5, ("layers", "mlp", "wu")),
        Leaf("wd", (L, f, d), f ** -0.5, ("layers", "mlp", "wd")),
    ]
    if s["bias"]:
        out += [
            Leaf("bq", (L, h, dh), 0.1, ("layers", "attn", "bq"), 1, heads),
            Leaf("bk", (L, kvh, dh), 0.1, ("layers", "attn", "bk")),
            Leaf("bv", (L, kvh, dh), 0.1, ("layers", "attn", "bv")),
        ]
    return out


def _layer_matmul_params(s: dict) -> int:
    d, h, kvh, dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    return d * h * dh + 2 * d * kvh * dh + h * dh * d + 3 * d * s["ff"]


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matmul per token (the ``N`` of 6N)."""
    s = sizes(config)
    return s["layers"] * _layer_matmul_params(s) + s["d"] * s["vocab"]


def train_flops(config: dict, tokens: int, pairs: int) -> int:
    """Forward plus backward FLOPs of ``tokens`` trained whose causal
    attention spans ``pairs`` query-key pairs."""
    s = sizes(config)
    attention_fwd = 4 * s["layers"] * s["heads"] * s["head_dim"] * int(pairs)
    return 6 * matmul_params(config) * int(tokens) + 3 * attention_fwd


def block_params(config: dict) -> list:
    """Published parameter count of each block, in the program's order:
    the embedding, each layer (norms and biases with it), the final
    norm."""
    s = sizes(config)
    d, h, kvh, dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    layer = _layer_matmul_params(s) + 2 * d
    if s["bias"]:
        layer += h * dh + 2 * kvh * dh
    return [s["vocab"] * d] + [layer] * s["layers"] + [d]


def groups(config: dict) -> list:
    """The reference's blocks, in the order of ``block_params``."""
    s = sizes(config)
    layer_keys = tuple(k for k in ref.LAYER_KEYS
                       if s["bias"] or k not in ("bq", "bk", "bv"))
    return [adagradselect.Group(0, 1, False, ("embed",)),
            adagradselect.Group(1, s["layers"], True, layer_keys),
            adagradselect.Group(s["layers"] + 1, 1, False, ("final_norm",))]


def reference_train(config: dict, seed: int, batches: list, *, opt: dict,
                    sel: dict, method: str, program_seed: int,
                    mode: str = "f32") -> dict:
    """The reference's first ``len(batches)`` steps from the seeded weights
    (drawn padded as the program pads, so that both take its bits)."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config["torch_dtype"])
    w0 = weights.canonical(seed, leaves(config), dtype)
    c = ref.consts(config)

    def row_loss(w, tokens, loss_mask, seg, pos):
        return ref.row_loss_sum(w, c, tokens, loss_mask, seg, pos, mode)

    return adagradselect.reference_train(
        row_loss, w0, batches, groups(config), opt=opt, sel=sel,
        method=method, program_seed=program_seed, param_dtype=dtype)
