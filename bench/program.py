"""The system under test, as the benchmark sees it: the program's model
configuration built from a configuration file under ``bench/configs``.

The file's published keys are the source of truth. The program's own entry
for the architecture (``repro_arch``) supplies only what the published
config does not say: the head and vocabulary padding it uses for sharding.
Every published key the program has a field for is set from the file.
"""
from __future__ import annotations

# published key -> ModelConfig field
_FIELDS = {
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


def program_config(config: dict):
    """``repro.configs.base.ModelConfig`` that runs ``config``."""
    from repro.configs import get_config
    base = get_config(config["repro_arch"])
    kw = {f: config[k] for k, f in _FIELDS.items() if k in config}
    if kw.get("head_dim") is None:
        kw["head_dim"] = int(config["hidden_size"]) // int(
            config["num_attention_heads"])
    if not config.get("tie_word_embeddings", False):
        raise ValueError("bench: the dense decoder reference ties embeddings")
    return base.replace(**kw)


def has_bias(config: dict) -> bool:
    return bool(config.get("attention_bias", False))
