"""The one place that knows the program under test: its configuration type
and its engine. Each model entry's family adapter
(`bench/families/<family>_program.py`) gives that model's configuration
and parameter layout; everything else in the benchmark speaks the
families' own layouts (`agreement.canonical_weights`)."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import families  # noqa: E402
from repro.config import CoSineConfig, ModelConfig  # noqa: E402
from repro.serving.engine import SpeculativeEngine  # noqa: E402


def model_config(m: dict) -> ModelConfig:
    """The program's configuration of one model entry of a config file."""
    return families.adapter(m["family"]).model_config(m)


def program_params(canon: dict, m: dict) -> dict:
    """One model's weights in its family's layout -> the program's."""
    return families.adapter(m["family"]).program_params(canon, m)


def build_engine(cfg: dict, models: list, canon: list, seed: int):
    """`SpeculativeEngine(strategy="cosine", backend="async")` over the
    planted weights; `models` is [target, drafter...] config entries."""
    tgt = (model_config(models[0]), program_params(canon[0], models[0]))
    drafters = [(model_config(m), program_params(c, m), f"domain{m['domain']}")
                for m, c in zip(models[1:], canon[1:])]
    cos = CoSineConfig(n_drafters=len(drafters), **cfg.get("cosine", {}))
    return SpeculativeEngine(tgt, drafters, cos, strategy="cosine",
                             max_len=int(cfg["serving"]["max_len"]),
                             seed=seed & 0x7FFFFFFF, backend="async")
