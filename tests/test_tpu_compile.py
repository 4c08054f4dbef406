"""Compile the chip's programs for a described TPU v5e, with no chip.

The TPU compiler refuses what the Pallas interpreter accepts (block
shapes off the (8, 128) tiling, programs that overflow HBM), so the five
kernels at serving widths and the full-width target verify and drafter
decode steps (on the slotted cache and on a snapshot) are compiled here for one v5e chip. Nothing runs: these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.configs import get_config
from repro.models import model as M
from repro.serving.runner import (_g_decode, _g_slot_decode, _g_slot_extend,
                                  _g_slot_verify)

HBM_BYTES = 15.75 * 2 ** 30        # v5e HBM the compiler may allocate

TARGET = get_config("qwen1.5-4b")
DRAFTER = get_config("qwen2-0.5b")
CASES = chip_smoke.kernel_cases(
    TARGET, DRAFTER, get_config("mamba2-130m"), max_len=chip_smoke.MAX_LEN,
    tree=chip_smoke.TREE, page=chip_smoke.PAGE, batch=chip_smoke.BATCH)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile
    cache off (its entries could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, case):
    name, op, _, make_args, _ = case
    args = _on(one_chip, jax.eval_shape(make_args, jax.random.PRNGKey(0)))
    compiled = jax.jit(lambda *a: op(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def _step_args(cfg, one_chip, tokens):
    """Shapes of a runner step at serving size: bf16 weights, a 9-slot
    (8 live + scratch) bf16 cache of MAX_LEN positions, 8 live rows."""
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, chip_smoke.BATCH + 1, chip_smoke.MAX_LEN,
        dtype=jnp.dtype(cfg.dtype)))
    rows = chip_smoke.BATCH
    i32 = jnp.int32
    return _on(one_chip, dict(
        params=params, cache=cache,
        tokens=jax.ShapeDtypeStruct((rows, tokens), i32),
        slot_idx=jax.ShapeDtypeStruct((rows,), i32)))


@pytest.mark.parametrize("step", ["target_verify", "target_prefill",
                                  "drafter_decode", "drafter_snapshot_decode"])
def test_full_width_step_fits_one_chip(one_chip, step):
    """The step alone fits one chip, and a step that writes the cache
    updates it in place: no temporary as large as one cache leaf (a
    relayout copy of the pool would not fit beside the drafters)."""
    rows = chip_smoke.BATCH
    if step == "target_verify":
        G = chip_smoke.TREE
        kw = _step_args(TARGET, one_chip, G)
        kw.update(_on(one_chip, dict(
            rel_pos=jax.ShapeDtypeStruct((rows, G), jnp.int32),
            seg_mask=jax.ShapeDtypeStruct((rows, G, G), jnp.bool_))))
        lowered = _g_slot_verify.lower(cfg=TARGET, **kw)
    elif step == "target_prefill":
        T = chip_smoke.PROMPT_LEN
        kw = _step_args(TARGET, one_chip, T)
        kw.update(_on(one_chip, dict(
            token_mask=jax.ShapeDtypeStruct((rows, T), jnp.bool_))))
        lowered = _g_slot_extend.lower(cfg=TARGET, page_view=None, **kw)
    elif step == "drafter_decode":
        kw = _step_args(DRAFTER, one_chip, 1)
        lowered = _g_slot_decode.lower(cfg=DRAFTER, **kw)
    else:
        # the greedy draft step: a snapshot of the live rows, no slot_idx
        kw = _step_args(DRAFTER, one_chip, 1)
        del kw["slot_idx"]
        kw["cache"] = _on(one_chip, jax.eval_shape(lambda: M.init_cache(
            DRAFTER, rows, chip_smoke.MAX_LEN, dtype=jnp.dtype(DRAFTER.dtype))))
        lowered = _g_decode.lower(cfg=DRAFTER, **kw)
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    largest_leaf = max(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(kw["cache"]))
    assert jax.tree.leaves(kw["params"])[0].dtype == jnp.bfloat16
    assert used < HBM_BYTES, (step, used / 2 ** 30)
    assert mem.temp_size_in_bytes < largest_leaf, (
        step, mem.temp_size_in_bytes / 2 ** 30)
