"""Seeded weights with drafter/target agreement planted in them.

Random weights make every drafter disagree with the target, so speculation
would be pure overhead. Here the weights are still drawn from the seed at
the published shapes, but three things are planted through a shared
low-rank token code:

* every token v has a unit code c_v (rank `code_rank` plus one axis per
  vocabulary domain, so tokens of one domain lie closer together);
* the target maps v to its successor T(v), a single long cycle through
  v's domain, and to a rival R(v) of that domain whose logit lies a
  seeded gap delta_v below T(v)'s (small gaps are the near-ties that a
  lower precision flips);
* drafter d maps v like the target with probability `p_in` on its own
  domain and `p_out` elsewhere, and to a random token of v's domain
  otherwise.

The map goes through the first layer's MLP in every model: the embedding
row of v holds its output code c_v (subspace A), its query q_v (subspace
B), a constant gate axis and a filler axis that gives every row one norm;
the MLP (silu gate held open by the constant axis) replaces A by G * q_v
and clears B, the gate axis and the filler, so the final hidden state
points at q_v and the head (the embedding itself when tied; its
transpose when untied) scores every token by c_u . q_v.

Codes and queries carry two more axes. Every code has 1 on the last, and
a query of strength sigma_v has sigma_v * t there (t = `head_offset` /
`gain`), which moves v's logits by sigma_v * t: the best logit of every
map sits near zero, where a float's rounding is smallest, so the check
sees the model's own rounding and not that of large logits. The other
axis takes the rest of the query's norm, sqrt(1 - sigma_v^2) of it, and
no code reads it: after the final norm a drafter off its domain
(sigma_v = `out_strength`) scores every token sigma_v times as far from
zero, so it is less confident there, and its best logit is still near
zero. All other residual-branch output
projections (`wo`, `wd`) are scaled so that together they add a random
direction of `branch_noise` times the residual stream's norm: every layer
still computes and still moves the logits, only less than the planted map.

Every tensor has the shape and dtype `init_params` gives it; only values
differ. `canonical_weights` returns the benchmark's own layout (stacked
per-layer arrays), which both the program adapter and the reference read.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

GATE = 4.0          # pre-activation of the held-open gate units


def padded_vocab(vocab: int, multiple: int = 256) -> int:
    """Rows of the embedding table as the program allocates them."""
    return -(-vocab // multiple) * multiple


# ------------------------------------------------------------ shared tables

@partial(jax.jit, static_argnames=("vocab", "code_rank", "domains",
                                   "domain_weight", "gap_mean"))
def _shared(key, *, vocab, code_rank, domains, domain_weight, gap_mean):
    nd = len(domains)
    dom = np.zeros(vocab, np.int32)
    for i, (lo, hi) in enumerate(domains):
        dom[lo:hi] = i
    dom = jnp.asarray(dom)
    k_code, k_perm, k_rival, k_gap = jax.random.split(key, 4)
    z = jax.random.normal(k_code, (vocab, code_rank), jnp.float32)
    z = z / math.sqrt(code_rank)
    onehot = jax.nn.one_hot(dom, nd, dtype=jnp.float32) * domain_weight
    codes = jnp.concatenate([z, onehot], axis=1)
    codes = codes / jnp.linalg.norm(codes, axis=1, keepdims=True)
    succ = jnp.zeros(vocab, jnp.int32)
    rival = jnp.zeros(vocab, jnp.int32)
    for i, (lo, hi) in enumerate(domains):
        n = hi - lo
        # one cycle through the whole domain: no short cycles
        order = lo + jax.random.permutation(jax.random.fold_in(k_perm, i), n)
        succ = succ.at[order].set(jnp.roll(order, -1))
        # rival: another token of the domain, never the successor
        off = jax.random.randint(jax.random.fold_in(k_rival, i), (n,), 1,
                                 n - 1)
        rival = rival.at[order].set(order[(jnp.arange(n) + 1 + off) % n])
    gap = jnp.minimum(jax.random.exponential(k_gap, (vocab,)) * gap_mean, 1.0)
    return codes, succ, rival, gap, dom


def shared_tables(seed: int, agreement: dict, vocab: int):
    """(codes (V, R) f32, succ (V,), rival (V,), gap (V,), dom (V,)) for a
    configuration, from the seed alone."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _shared(jax.random.fold_in(key, 0), vocab=vocab,
                   code_rank=int(agreement["code_rank"]),
                   domains=tuple(tuple(d) for d in agreement["domains"]),
                   domain_weight=float(agreement["domain_weight"]),
                   gap_mean=float(agreement["rival_gap_mean"]))


def model_key(seed: int, index: int):
    """Key of model `index` (0 = target, i + 1 = drafter i)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.random.fold_in(key, 1 + index)


@jax.jit
def target_queries(codes, succ, rival, gap):
    """(V, R) unit query of the target: its successor, with the rival a
    gap behind."""
    q = codes[succ] + (1.0 - gap)[:, None] * codes[rival]
    return q / jnp.linalg.norm(q, axis=1, keepdims=True)


@partial(jax.jit, static_argnames=("own",))
def drafter_queries(key, tq, codes, rival, gap, dom, *, own, p_in, p_out):
    """(queries (V, R), agrees (V,) bool) of a drafter expert on domain
    `own`: the target's query with probability p_in on its domain and
    p_out elsewhere, else the code of a random token of v's domain."""
    vocab = codes.shape[0]
    k_a, k_w = jax.random.split(key)
    p = jnp.where(dom == own, p_in, p_out)
    agree = jax.random.uniform(k_a, (vocab,)) < p
    # a random token of the same domain: shift within the domain's slice
    first = jnp.searchsorted(dom, dom, side="left").astype(jnp.int32)
    last = jnp.searchsorted(dom, dom, side="right").astype(jnp.int32)
    lo, size = first, last - first
    off = jax.random.randint(k_w, (vocab,), 1, 1 << 30) % jnp.maximum(size, 1)
    other = lo + (jnp.arange(vocab) - lo + off) % jnp.maximum(size, 1)
    # a wrong query keeps the target's structure (a rival a gap behind),
    # so a drafter is no more or less confident where it is wrong
    wrong = codes[other] + (1.0 - gap)[:, None] * codes[rival[other]]
    wrong = wrong / jnp.linalg.norm(wrong, axis=1, keepdims=True)
    q = jnp.where(agree[:, None], tq, wrong)
    return q, agree


# ------------------------------------------------------------ weights

def _dense(key, shape, dtype):
    """`init_params`'s dense draw: N(0, 1/fan_in), fan_in = shape[-2]."""
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(shape[-2])).astype(dtype)


@partial(jax.jit, static_argnames=("spec", "rows", "plant"))
def _weights(key, queries, strength, codes, *, spec, rows, plant):
    (n_layers, d, hq, hkv, hd, ff, vocab, qkv_bias, tied) = spec
    (logit_scale, gain, gate_axis, head_offset, branch_noise) = plant
    dt = jnp.bfloat16
    V = codes.shape[0]
    ks = jax.random.split(key, 12)
    # codes and queries with the offset axis (last) and the axis that
    # keeps every query's norm (second last); see the module's docstring
    t = head_offset / gain
    sig = strength[:, None]
    codes = jnp.concatenate([codes, jnp.zeros((V, 1)), jnp.ones((V, 1))], 1)
    q = jnp.concatenate([sig * queries,
                         jnp.sqrt((1.0 - sig ** 2) * (1.0 + t * t)),
                         sig * t], axis=1)
    R = codes.shape[1]
    # orthonormal frame: A (R), B (R), gate axis, filler axis
    basis, _ = jnp.linalg.qr(jax.random.normal(ks[0], (d, 2 * R + 2)))
    QA, QB = basis[:, :R], basis[:, R: 2 * R]
    e0, e1 = basis[:, 2 * R], basis[:, 2 * R + 1]
    sc = jnp.sum(codes * codes, axis=1)
    sq = jnp.sum(q * q, axis=1)
    total = jnp.max(sc) + jnp.max(sq) + gate_axis ** 2 + 0.25
    fill = jnp.sqrt(total - sc - sq - gate_axis ** 2)
    s = logit_scale / math.sqrt(d)
    emb = s * (codes @ QA.T + q @ QB.T + gate_axis * e0[None, :]
               + fill[:, None] * e1[None, :])
    pad = jax.random.normal(ks[1], (rows - vocab, d)) * (s / math.sqrt(d))
    embed = jnp.concatenate([emb, pad], axis=0).astype(dt)

    L = n_layers
    # every residual branch adds about 1.5 * mult * sqrt(d) of random
    # direction; over L layers that is branch_noise times the stream's norm
    mult = branch_noise * s * jnp.sqrt(total) / (1.5 * math.sqrt(L * d))
    w = {
        "ln1": jnp.ones((L, d), dt),
        "wq": _dense(ks[2], (L, d, hq * hd), dt),
        "wk": _dense(ks[3], (L, d, hkv * hd), dt),
        "wv": _dense(ks[4], (L, d, hkv * hd), dt),
        "wo": (_dense(ks[5], (L, hq * hd, d), jnp.float32)
               * mult).astype(dt),
        "ln2": jnp.ones((L, d), dt),
        "wg": _dense(ks[6], (L, d, ff), dt),
        "wu": _dense(ks[7], (L, d, ff), dt),
    }
    if qkv_bias:
        w["bq"] = jnp.zeros((L, hq * hd), dt)
        w["bk"] = jnp.zeros((L, hkv * hd), dt)
        w["bv"] = jnp.zeros((L, hkv * hd), dt)
    wd = _dense(ks[8], (L, ff, d), jnp.float32) * mult
    # layer 0's MLP: units [0, R) read B and write G*A - B; units [R, 2R)
    # read A and write -A; unit 2R reads the filler axis and clears it.
    # The RMS norm in front scales the row to norm sqrt(d)/sqrt(total);
    # silu(GATE) holds every used unit's gate open by the same factor.
    lam = math.sqrt(d) / jnp.sqrt(total)
    act = GATE * jax.nn.sigmoid(GATE)
    # unit 2R+1 reads the gate axis and clears it
    n_used = 2 * R + 2
    read = jnp.concatenate([QB, QA, e1[:, None], e0[:, None]], axis=1)
    write = jnp.concatenate(
        [gain * QA.T - QB.T, -QA.T, -e1[None, :], -e0[None, :]],
        axis=0) * (s / (lam * act))                                # (n, d)
    gate = jnp.broadcast_to((GATE / (lam * gate_axis)) * e0[:, None],
                            (d, n_used))
    w["wu"] = w["wu"].at[0, :, :n_used].set(read.astype(dt))
    w["wg"] = w["wg"].at[0, :, :n_used].set(gate.astype(dt))
    wd = wd.at[0, :n_used, :].set(write)
    w["wd"] = wd.astype(dt)
    out = {"embed": embed, "layers": w, "final_norm": jnp.ones((d,), dt)}
    if not tied:
        out["head"] = embed.T
    return out


def spec_tuple(m: dict):
    """Hashable sizes of one model entry of a configuration file."""
    return (int(m["n_layers"]), int(m["d_model"]), int(m["n_heads"]),
            int(m["n_kv_heads"]), int(m["head_dim"]), int(m["d_ff"]),
            int(m["vocab"]), bool(m["qkv_bias"]), bool(m["tie_embeddings"]))


def plant_tuple(agreement: dict):
    """Hashable planting constants of a configuration."""
    return (float(agreement["logit_scale"]), float(agreement["gain"]),
            float(agreement["gate_axis"]), float(agreement["head_offset"]),
            float(agreement["branch_noise"]))


def canonical_weights(seed: int, index: int, model: dict, agreement: dict,
                      queries, strength, codes):
    """One model's weights in the benchmark's layout, drawn on the device
    in one jitted call: {"embed" (rows, d), ["head" (d, rows)],
    "final_norm" (d,), "layers": {name: (n_layers, ...)}}, all bf16."""
    return _weights(model_key(seed, index), queries, strength, codes,
                    spec=spec_tuple(model),
                    rows=padded_vocab(int(model["vocab"])),
                    plant=plant_tuple(agreement))


def plant_all(seed: int, cfg: dict):
    """Every model's canonical weights for a configuration, target first,
    plus the shared tables (for prompts and tests)."""
    ag = cfg["agreement"]
    tgt = cfg["target"]
    codes, succ, rival, gap, dom = shared_tables(seed, ag, int(tgt["vocab"]))
    tq = target_queries(codes, succ, rival, gap)
    ones = jnp.ones(codes.shape[0], jnp.float32)
    out = [canonical_weights(seed, 0, tgt, ag, tq, ones, codes)]
    for i, dcfg in enumerate(drafter_list(cfg)):
        q, _ = drafter_queries(jax.random.fold_in(model_key(seed, i + 1), 7),
                               tq, codes, rival, gap, dom, own=int(dcfg["domain"]),
                               p_in=float(ag["p_in"]), p_out=float(ag["p_out"]))
        strength = jnp.where(dom == int(dcfg["domain"]), 1.0,
                             float(ag["out_strength"]))
        out.append(canonical_weights(seed, i + 1, dcfg, ag, q, strength,
                                     codes))
    tables = {"codes": codes, "succ": succ, "rival": rival, "gap": gap,
              "dom": dom}
    return out, tables


def drafter_list(cfg: dict):
    """One entry per drafter node, each with its sizes and its domain."""
    out = []
    for d in cfg["drafters"]:
        for dom in d["domains"]:
            m = dict(d["model"])
            m["domain"] = dom
            out.append(m)
    return out
