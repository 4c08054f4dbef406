"""Model families, found by the name a configuration gives them.

Every model entry of a configuration file (the target and each drafter)
names its family under "family". The harness then finds two modules by
that name, and names no family itself:

* `bench/families/<family>.py`, the benchmark's side, which imports
  nothing of the program under test:
  - `REQUIRED`: the keys its model entries must hold;
  - `plant(key, queries, strength, codes, model, agreement)`: the
    model's weights in the family's own layout, drawn on the device from
    `key`, with the planted map of `bench/agreement.py` in them;
  - `forward(weights, model, tokens, fp8)`: the plain reference, float32
    logits of one sequence at `Precision.HIGHEST`, or with `fp8` the
    control (`bench/reference.py` holds the shared helpers);
  - `weight_bytes(model, dtype_bytes)`, `token_flops(model, context)` and
    `call_least_seconds(model, tokens_at, peak)`: the counts of
    `bench/roofline.py`, with the bytes a call must read (a family whose
    call reads only part of its weights counts only that part).
* `bench/families/<family>_program.py`, the adapter, the only file of a
  family that imports the program: `model_config(model)` (the program's
  `ModelConfig`) and `program_params(canon, model)` (the family's layout
  -> the program's parameters).

Adding a family is adding those two files.
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType

NAME = re.compile(r"[a-z][a-z0-9_]*")


class ConfigError(ValueError):
    """A model entry that names no family, an unknown one, or lacks one of
    its family's keys."""


def _module(name: str, suffix: str = "") -> ModuleType:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ConfigError(f"family {name!r} is not a family name")
    try:
        return importlib.import_module(f"{__name__}.{name}{suffix}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}{suffix}":
            raise
        raise ConfigError(f"no family {name!r} (bench/families/"
                          f"{name}{suffix}.py)") from e


def family(name: str) -> ModuleType:
    """The benchmark's side of family `name`."""
    return _module(name)


def adapter(name: str) -> ModuleType:
    """The program's side (the adapter) of family `name`."""
    return _module(name, "_program")


def of(model: dict) -> ModuleType:
    """The family of one model entry."""
    return family(model["family"])


def check_entry(model: dict, where: str) -> None:
    """A model entry names a family that loads and holds its keys; the
    error names `where` (the configuration's file)."""
    if "family" not in model:
        raise ConfigError(f"{where}: model {model.get('name')!r} names no "
                          f"family")
    try:
        fam = of(model)
    except ConfigError as e:
        raise ConfigError(f"{where}: {e}") from e
    missing = [k for k in fam.REQUIRED if k not in model]
    if missing:
        raise ConfigError(f"{where}: model {model.get('name')!r} of family "
                          f"{model['family']!r} lacks {missing}")
