"""The dense family's adapter to the program under test: its configuration
and its parameter layout."""
from __future__ import annotations

import bench.program  # noqa: F401  (puts the program on the path)
from repro.config import ModelConfig


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


def program_params(canon: dict, m: dict) -> dict:
    """The dense layout -> `init_params`'s (the same arrays, no copy): one
    stage of identical layers, stacked on axis 0."""
    w = canon["layers"]
    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
             if k in w}
    sub = {"ln1": {"scale": w["ln1"]}, "mixer": mixer,
           "ln2": {"scale": w["ln2"]},
           "ffn": {"wg": w["wg"], "wu": w["wu"], "wd": w["wd"]}}
    out = {"embed": canon["embed"], "stages": [(sub,)],
           "final_norm": {"scale": canon["final_norm"]}}
    if not bool(m["tie_embeddings"]):
        out["head"] = canon["head"]
    return out
