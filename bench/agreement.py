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

Each model's map is a unit query q_v per token (`target_queries`,
`drafter_queries`) with a strength sigma_v (1, or `out_strength` for a
drafter off its domain), and a model scores token u after v by
c_u . q_v. How a model's weights carry that map is its family's
(`bench/families/<family>.py` `plant`): `canonical_weights` returns the
weights in the family's own layout, which both the program adapter and
the reference read.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


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

def canonical_weights(seed: int, index: int, model: dict, agreement: dict,
                      queries, strength, codes):
    """One model's weights in its family's layout, drawn on the device in
    one jitted call by the family's `plant`."""
    return families.of(model).plant(model_key(seed, index), queries,
                                    strength, codes, model, agreement)


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
