"""The one place the benchmark builds the program's own config object
from a configuration file, and checks the file tells the truth."""

from __future__ import annotations

import dataclasses

MODEL_KEYS = ("vocab_size", "num_annotations", "local_dim", "global_dim",
              "key_dim", "num_heads", "num_blocks", "narrow_kernel",
              "wide_kernel", "wide_dilation")


def _replace(node, path, value):
    head, _, rest = path.partition(".")
    if rest:
        value = _replace(getattr(node, head), rest, value)
    elif isinstance(value, list):
        value = tuple(value)
    return dataclasses.replace(node, **{head: value})


def program_config(config: dict, overrides: dict):
    """The preset the file names, with the file's and the cell's
    overrides; refuses a file whose sizes are not the program's."""
    from proteinbert_tpu.configs.config import get_preset

    cfg = get_preset(config["preset"])
    for path, value in {**config.get("overrides", {}), **overrides}.items():
        cfg = _replace(cfg, path, value)
    for key in MODEL_KEYS + ("dtype", "param_dtype", "remat", "remat_policy"):
        if getattr(cfg.model, key) != config[key]:
            raise SystemExit(
                f"configuration file says {key}={config[key]!r}, the "
                f"program runs {getattr(cfg.model, key)!r}")
    for group in ("optimizer", "corruption"):
        node = cfg.optimizer if group == "optimizer" else cfg.data
        for key, value in config[group].items():
            if getattr(node, key) != value:
                raise SystemExit(
                    f"configuration file says {group}.{key}={value!r}, "
                    f"the program runs {getattr(node, key)!r}")
    return cfg


def model_sizes(config: dict) -> dict:
    return {k: config[k] for k in MODEL_KEYS}
