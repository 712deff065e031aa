"""The system under test, as the benchmark sees it: the program's model
configuration built from a configuration file under ``bench/configs``.

The file's published keys are the source of truth. The program's own entry
for the architecture (``repro_arch``) supplies only what the published
config does not say: the padding it uses for sharding. A family module
(``bench/families/``) names which published key sets which field.
"""
from __future__ import annotations


def model_config(config: dict, fields: dict, **derived):
    """``repro.configs.base.ModelConfig`` that runs ``config``: every
    published key of ``fields`` (published key -> field) that the file has,
    then ``derived`` (field -> value) for what the file leaves unsaid."""
    from repro.configs import get_config
    base = get_config(config["repro_arch"])
    kw = {f: config[k] for k, f in fields.items() if k in config}
    for f, value in derived.items():
        if kw.get(f) is None:
            kw[f] = value
    return base.replace(**kw)
