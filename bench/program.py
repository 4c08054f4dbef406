"""The one place that knows the program under test: its configuration type,
its parameter layout and its engine. Everything else in the benchmark
speaks the benchmark's own layout (`agreement.canonical_weights`)."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.config import CoSineConfig, ModelConfig  # noqa: E402
from repro.serving.engine import SpeculativeEngine  # noqa: E402


def model_config(m: dict) -> ModelConfig:
    """The program's configuration of one model entry of a config file."""
    return ModelConfig(
        name=m["name"], family="dense", n_layers=int(m["n_layers"]),
        d_model=int(m["d_model"]), n_heads=int(m["n_heads"]),
        n_kv_heads=int(m["n_kv_heads"]), head_dim=int(m["head_dim"]),
        d_ff=int(m["d_ff"]), vocab=int(m["vocab"]),
        qkv_bias=bool(m["qkv_bias"]),
        tie_embeddings=bool(m["tie_embeddings"]),
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["norm_eps"]),
        attention="full", dtype="bfloat16")


def program_params(canon: dict, tied: bool) -> dict:
    """The benchmark's layout -> `init_params`'s (the same arrays, no
    copy): one stage of identical layers, stacked on axis 0."""
    w = canon["layers"]
    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
             if k in w}
    sub = {"ln1": {"scale": w["ln1"]}, "mixer": mixer,
           "ln2": {"scale": w["ln2"]},
           "ffn": {"wg": w["wg"], "wu": w["wu"], "wd": w["wd"]}}
    out = {"embed": canon["embed"], "stages": [(sub,)],
           "final_norm": {"scale": canon["final_norm"]}}
    if not tied:
        out["head"] = canon["head"]
    return out


def build_engine(cfg: dict, models: list, canon: list, seed: int):
    """`SpeculativeEngine(strategy="cosine", backend="async")` over the
    planted weights; `models` is [target, drafter...] config entries."""
    tgt = (model_config(models[0]),
           program_params(canon[0], bool(models[0]["tie_embeddings"])))
    drafters = [(model_config(m), program_params(c, bool(m["tie_embeddings"])),
                 f"domain{m['domain']}")
                for m, c in zip(models[1:], canon[1:])]
    cos = CoSineConfig(n_drafters=len(drafters), **cfg.get("cosine", {}))
    return SpeculativeEngine(tgt, drafters, cos, strategy="cosine",
                             max_len=int(cfg["serving"]["max_len"]),
                             seed=seed & 0x7FFFFFFF, backend="async")
