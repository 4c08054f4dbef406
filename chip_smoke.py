#!/usr/bin/env python3
"""Chip smoke run: CoSine's served path once on one TPU, at published widths.

    python chip_smoke.py            # from the repo root, on a TPU host

Two phases, each a function of its configurations so that the CPU tests
run them at tiny size (tests/test_chip_smoke.py); only `main()` insists
on a TPU.

* kernel phase — the five Pallas kernels compiled with no interpreter at
  serving widths, run, and compared with their jnp oracles;
* serving phase — `SpeculativeEngine(strategy="cosine", backend="async")`
  with a qwen1.5-4b target and two qwen2-0.5b drafters in bf16 (random
  weights from a seed): 4 requests of 128 prompt tokens and 32 new tokens
  each, arriving together, served to completion twice (a cold wave that
  compiles and a warm wave), then every committed stream is checked
  against the target's own greedy decode through the same runner.

Every number printed is one smoke pass's, not a benchmark's. The last
line of standard output is the JSON verdict; it is printed only when
every phase passed, and the script exits non-zero otherwise (also when
JAX finds no TPU).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import CoSineConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels.decode_attention.ops import (  # noqa: E402
    decode_attention, decode_attention_paged)
from repro.kernels.decode_attention.ref import (  # noqa: E402
    decode_attention_paged_ref, decode_attention_ref)
from repro.kernels.int8_gemv.ops import int8_gemv  # noqa: E402
from repro.kernels.int8_gemv.ref import int8_gemv_ref  # noqa: E402
from repro.kernels.ssd_scan.ops import ssd  # noqa: E402
from repro.kernels.tree_attention.ops import tree_attention  # noqa: E402
from repro.kernels.tree_attention.ref import tree_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.ssm import ssd_reference  # noqa: E402
from repro.serving.engine import SpeculativeEngine  # noqa: E402

MAX_LEN = 1024          # cache positions per slot (9 slots: 8 live + scratch)
TREE = 16               # verified tree tokens the kernel phase compiles for
PAGE = 64               # paged-pool page size
BATCH = 8               # live requests the kernel phase compiles for
N_REQUESTS = 4
PROMPT_LEN = 128
NEW_TOKENS = 32

# Kernel tolerances, |out - oracle| <= atol + rtol * |oracle| elementwise;
# the oracles run in f32 at matmul precision "highest".
# Attention: operands are bf16, so q.k is exact in f32 products; the
# MXU may round the f32 softmax weights to bf16 for the p.v product
# (2^-9 relative), which bounds the error of a convex combination of
# unit-normal values by ~2^-9 * max|v| < 1e-2. 2e-2 leaves a factor 2.
ATTN_TOL = (2e-2, 2e-2)
# SSD: f32 operands that the MXU may take as bf16 (2^-9 relative each);
# outputs are 128-term sums of products of O(1) values over a decaying
# state, so the error stays near 2^-9 of the output scale: 2e-2 rtol and
# an absolute floor for outputs near zero.
SSD_TOL = (2e-2, 5e-2)
# int8 GEMV: bf16 activations times int8 weights are exact in f32, so
# only the order of the K-term f32 summation differs from the oracle.
GEMV_TOL = (1e-4, 1e-3)
# Stream check: logits leave the model as bf16 (8 significant bits, one
# unit in the last place is at most 2^-7 of the value). A 16-token tree
# verify and a one-token decode round the residual stream at different
# points in every sublayer, so where the target's two best tokens lie
# within 4 such units of the top logit, either may win: a committed
# token may differ from the reference argmax only by at most
# NEAR_TIE_REL * |top logit|.
NEAR_TIE_REL = 4 * 2.0 ** -7


def say(msg: str) -> None:
    """One line of smoke-run output (flushed: the run may be cut)."""
    print(f"smoke: {msg}", flush=True)


@contextmanager
def compile_seconds():
    """Sum of backend compile time (and count) inside the block."""
    acc = {"s": 0.0, "n": 0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc["s"] += duration
            acc["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


# ------------------------------------------------------------ kernel phase

def kernel_cases(target, drafter, ssm_cfg, *, max_len, tree, page, batch):
    """The five kernels at these widths: [(name, op, oracle, make_args,
    (rtol, atol))]. `op(*args, interpret=...)` and `oracle(*args)` give
    the same structure; `make_args(key)` draws the inputs, so
    `jax.eval_shape(make_args, key)` gives their shapes alone."""
    def attn_widths(cfg):
        return (cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
                cfg.resolved_head_dim)

    def normal(key, shape, dtype=jnp.bfloat16):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    def decode_args(cfg):
        H, G, D = attn_widths(cfg)

        def make(key):
            ks = jax.random.split(key, 3)
            cp = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32),
                                  (batch, max_len))
            # rows hold different lengths; unwritten columns are -1
            lens = max_len - 8 * jnp.arange(batch, dtype=jnp.int32) - 1
            cp = jnp.where(cp < lens[:, None], cp, -1)
            return (normal(ks[0], (batch, H, G, D)),
                    normal(ks[1], (batch, H, max_len, D)),
                    normal(ks[2], (batch, H, max_len, D)), cp, lens)
        return make, D ** -0.5

    def tree_args(cfg):
        H, G, D = attn_widths(cfg)
        R = tree * G

        def make(key):
            ks = jax.random.split(key, 6)
            cp = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32),
                                  (batch, max_len))
            n = max_len - tree
            cp = jnp.where(cp < n, cp, -1)
            depth = jnp.repeat(jnp.arange(tree, dtype=jnp.int32) // 2, G)
            qp = n + jnp.broadcast_to(depth, (batch, R))
            node = jnp.repeat(jnp.arange(tree), G)
            # ancestor mask: each node sees itself and a random subset
            mask = (jax.random.bernoulli(ks[5], 0.5, (batch, R, tree))
                    & (node[:, None] > jnp.arange(tree)[None, :]))
            mask = mask | (node[:, None] == jnp.arange(tree)[None, :])
            return (normal(ks[0], (batch, H, R, D)),
                    normal(ks[1], (batch, H, max_len, D)),
                    normal(ks[2], (batch, H, max_len, D)), cp,
                    normal(ks[3], (batch, H, tree, D)),
                    normal(ks[4], (batch, H, tree, D)), qp, mask)
        return make, D ** -0.5

    def paged_args(cfg):
        H, G, D = attn_widths(cfg)
        nv = max_len // page
        P = 2 + batch * nv               # SCRATCH + NULL + every view page

        def make(key):
            ks = jax.random.split(key, 4)
            # request b owns pages 2 + b*nv ... in a shuffled order; its
            # length leaves the tail of its last pages empty
            perm = jax.random.permutation(ks[3], batch * nv) + 2
            tables = perm.reshape(batch, nv).astype(jnp.int32)
            lens = max_len - 8 * jnp.arange(batch, dtype=jnp.int32) - 1
            col = jnp.arange(nv * page, dtype=jnp.int32).reshape(nv, page)
            rows = jnp.where(col[None] < lens[:, None, None], col[None], -1)
            page_pos = jnp.full((P, page), -1, jnp.int32).at[
                tables.reshape(-1)].set(rows.reshape(-1, page))
            return (normal(ks[0], (batch, H, G, D)),
                    normal(ks[1], (P, H, page, D)),
                    normal(ks[2], (P, H, page, D)), page_pos, lens, tables)
        return make, D ** -0.5

    def ssd_args(key):
        s = ssm_cfg.ssm
        H, P, N = s.n_heads(ssm_cfg.d_model), s.head_dim, s.d_state
        G, L = s.n_groups, 4 * s.chunk_size
        ks = jax.random.split(key, 6)
        return (normal(ks[0], (1, L, H, P)),
                jax.nn.softplus(jax.random.normal(ks[1], (1, L, H)) - 2.0),
                -jnp.exp(jax.random.normal(ks[2], (H,))),
                jax.random.normal(ks[3], (1, L, G, N)) * N ** -0.5,
                jax.random.normal(ks[4], (1, L, G, N)) * N ** -0.5,
                jax.random.normal(ks[5], (1, H, P, N)) * 0.1)

    def gemv_args(key):
        K, N = drafter.d_model, drafter.d_ff
        ks = jax.random.split(key, 3)
        return (normal(ks[0], (batch, K)),
                jax.random.randint(ks[1], (K, N), -127, 128, jnp.int32)
                .astype(jnp.int8),
                jax.random.uniform(ks[2], (N,), jnp.float32, 1e-3, 1e-2))

    cases = []
    for name, cfg in (("decode_attention[target]", target),
                      ("decode_attention[drafter]", drafter)):
        make, scale = decode_args(cfg)
        cases.append((name, partial(decode_attention, scale=scale),
                      partial(decode_attention_ref, scale=scale), make,
                      ATTN_TOL))
    make, scale = tree_args(target)
    cases.append(("tree_attention[target]",
                  partial(tree_attention, scale=scale),
                  partial(tree_attention_ref, scale=scale), make, ATTN_TOL))
    make, scale = paged_args(target)
    cases.append(("decode_attention_paged[target]",
                  partial(decode_attention_paged, scale=scale),
                  partial(decode_attention_paged_ref, scale=scale), make,
                  ATTN_TOL))
    chunk = ssm_cfg.ssm.chunk_size
    cases.append((f"ssd_scan[{ssm_cfg.name}]",
                  lambda x, dt, A, B, C, s0, interpret=None: ssd(
                      x, dt, A, B, C, chunk=chunk, initial_state=s0,
                      interpret=interpret),
                  lambda x, dt, A, B, C, s0: ssd_reference(
                      x, dt, A, B, C, initial_state=s0),
                  ssd_args, SSD_TOL))
    cases.append((f"int8_gemv[{drafter.name}]", int8_gemv, int8_gemv_ref,
                  gemv_args, GEMV_TOL))
    return cases


def kernel_phase(cases, *, interpret: bool, seed: int = 0):
    """Compile, run and check each case; returns per-kernel results.
    Without the interpreter, the compiled program must hold the Mosaic
    kernel (`tpu_custom_call`)."""
    results = []
    for i, (name, op, oracle, make_args, (rtol, atol)) in enumerate(cases):
        args = jax.jit(make_args)(jax.random.PRNGKey(seed + i))
        t0 = time.perf_counter()
        compiled = jax.jit(partial(op, interpret=interpret)).lower(
            *args).compile()
        compile_s = time.perf_counter() - t0
        if not interpret and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the program")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(oracle)(*args)
        worst = 0.0
        for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            o = np.asarray(o, np.float32)
            r = np.asarray(r, np.float32)
            if o.shape != r.shape or not np.all(np.isfinite(o)):
                raise AssertionError(f"{name}: bad output {o.shape}")
            excess = np.abs(o - r) - (atol + rtol * np.abs(r))
            worst = max(worst, float(np.max(np.abs(o - r))))
            if np.any(excess > 0):
                raise AssertionError(
                    f"{name}: max |out - oracle| {worst} beyond "
                    f"rtol {rtol} atol {atol}")
        results.append({"kernel": name, "compile_s": compile_s,
                        "run_s": run_s, "max_abs_err": worst,
                        "rtol": rtol, "atol": atol})
    return results


# ----------------------------------------------------------- serving phase

def init_weights(cfg, seed: int):
    """Seeded random weights in cfg.dtype, drawn on the device."""
    return jax.block_until_ready(
        jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(seed),
                                                 cfg))


def seeded_prompts(vocab: int, n: int, length: int, seed: int):
    """Prompt ids drawn over the whole vocabulary."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, length).tolist() for _ in range(n)]


def check_streams(runner, prompts, streams, rid_base: int):
    """Teacher-force each committed stream through the runner's own
    prefill + incremental decode and compare it with the target's argmax
    at every position. A differing token is allowed only at a near-tie
    (its logit within NEAR_TIE_REL * |top logit| of the top); returns
    the number of such positions and the largest gap seen there."""
    rids = [rid_base + i for i in range(len(prompts))]
    first = runner.prefill_requests(dict(zip(rids, prompts)))
    logits = np.stack([first[r][0] for r in rids])
    n_near, worst = 0, 0.0
    n = len(streams[0])
    try:
        for j in range(n):
            for b, s in enumerate(streams):
                top = int(np.argmax(logits[b]))
                if s[j] == top:
                    continue
                gap = float(logits[b, top] - logits[b, s[j]])
                tol = NEAR_TIE_REL * abs(float(logits[b, top]))
                if gap > tol:
                    raise AssertionError(
                        f"request {b} token {j}: committed {s[j]}, target "
                        f"argmax {top}, logit gap {gap} > {tol}")
                n_near += 1
                worst = max(worst, gap)
            if j + 1 < n:
                logits, _ = runner.decode(rids, [s[j] for s in streams])
    finally:
        for r in rids:
            runner.drop(r)
    return n_near, worst


def serving_phase(target, drafters, *, max_len, n_requests, prompt_len,
                  new_tokens, waves=2, seed=0):
    """Serve `waves` waves of `n_requests` seeded requests through the
    async CoSine engine and check every stream; returns per-wave stats."""
    tcfg, _ = target
    cos = CoSineConfig(n_drafters=len(drafters))
    eng = SpeculativeEngine(target, drafters, cos, strategy="cosine",
                            max_len=max_len, seed=seed, backend="async")
    out = []
    try:
        for w in range(waves):
            prompts = seeded_prompts(tcfg.vocab, n_requests, prompt_len,
                                     seed + 1 + w)
            n_rec = len(eng.stats.records)
            with compile_seconds() as comp:
                t0 = time.perf_counter()
                reqs = [eng.submit(p, max_new_tokens=new_tokens)
                        for p in prompts]
                eng.run()
                jax.block_until_ready(eng.target.slots.cache)
                wall_s = time.perf_counter() - t0
            recs = eng.stats.records[n_rec:]
            for r in reqs:
                if not r.done or len(r.generated) != new_tokens:
                    raise AssertionError(
                        f"request {r.rid}: done={r.done}, "
                        f"{len(r.generated)} of {new_tokens} tokens")
            streams = [list(map(int, r.generated)) for r in reqs]
            with compile_seconds() as ref_comp:
                t0 = time.perf_counter()
                n_near, worst = check_streams(eng.target, prompts, streams,
                                              rid_base=10_000 * (w + 1))
                ref_s = time.perf_counter() - t0
            committed = sum(r.committed for r in recs)
            rows = sum(r.batch for r in recs)
            out.append({
                "wave": w, "requests": len(reqs), "tokens": committed,
                "verifies": len(recs),
                # per request per verification it took part in
                "tokens_per_verify": committed / max(rows, 1),
                "verify_ms": sum(r.verify_ms for r in recs),
                "draft_ms": sum(r.draft_ms for r in recs),
                "wall_s": wall_s, "compile_s": comp["s"],
                "compiles": comp["n"], "check_wall_s": ref_s,
                "check_compile_s": ref_comp["s"],
                "near_ties": n_near, "near_tie_max_gap": worst})
    finally:
        eng.backend.shutdown()
    return out


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    say(f"compile cache {enable_compile_cache()}")
    say("numbers below are one smoke pass's, not a benchmark's")
    say(f"device_kind={dev.device_kind!r} count={len(jax.devices())}")

    target_cfg = get_config("qwen1.5-4b")
    drafter_cfg = get_config("qwen2-0.5b")
    ssm_cfg = get_config("mamba2-130m")
    for role, c in (("target", target_cfg), ("drafter x2", drafter_cfg)):
        say(f"{role} {c.name} dtype={c.dtype} layers={c.n_layers} "
            f"d_model={c.d_model} heads={c.n_heads}/{c.n_kv_heads} "
            f"head_dim={c.resolved_head_dim} vocab={c.vocab}")

    t0 = time.perf_counter()
    cases = kernel_cases(target_cfg, drafter_cfg, ssm_cfg, max_len=MAX_LEN,
                         tree=TREE, page=PAGE, batch=BATCH)
    for r in kernel_phase(cases, interpret=False, seed=args.seed):
        say(f"kernel {r['kernel']} compiled (no interpreter) in "
            f"{r['compile_s']:.3f}s, ran in {r['run_s'] * 1e3:.3f}ms, "
            f"max |out - oracle| {r['max_abs_err']:.3e} "
            f"(rtol {r['rtol']}, atol {r['atol']})")
    say(f"kernel phase passed, wall {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    with compile_seconds() as comp:
        target = (target_cfg, init_weights(target_cfg, args.seed))
        drafters = [(drafter_cfg, init_weights(drafter_cfg, args.seed + 1 + i),
                     f"node{i}") for i in range(2)]
    say(f"weights drawn in {time.perf_counter() - t0:.1f}s "
        f"(compile {comp['s']:.1f}s), bytes_in_use="
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")

    waves = serving_phase(target, drafters, max_len=MAX_LEN,
                          n_requests=N_REQUESTS, prompt_len=PROMPT_LEN,
                          new_tokens=NEW_TOKENS, seed=args.seed)
    for w in waves:
        say(f"serving wave {w['wave']}: {w['requests']}/{N_REQUESTS} "
            f"requests completed via AsyncJaxBackend, {w['tokens']} tokens "
            f"in {w['verifies']} batched verifies, "
            f"{w['tokens_per_verify']:.3f} tokens per request per verify; "
            f"wall {w['wall_s']:.3f}s incl. "
            f"{w['compiles']} compiles {w['compile_s']:.1f}s; verify span "
            f"total {w['verify_ms']:.1f}ms, draft span total "
            f"{w['draft_ms']:.1f}ms")
        say(f"stream check wave {w['wave']} passed: near-tie positions "
            f"{w['near_ties']} (largest gap {w['near_tie_max_gap']:.4f}); "
            f"reference wall {w['check_wall_s']:.1f}s incl. compile "
            f"{w['check_compile_s']:.1f}s")
    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
