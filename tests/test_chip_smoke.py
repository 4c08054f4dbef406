"""chip_smoke.py's phases at tiny size on the CPU (kernels interpreted),
its stream check, and its refusal to run without a TPU."""
import jax
import numpy as np
import pytest

import chip_smoke
from conftest import TINY_MAX_LEN, tiny_model_cfg
from repro.config import ModelConfig


def _drafter_cfg():
    return ModelConfig(name="tiny-draft", family="dense", n_layers=1,
                       d_model=48, n_heads=2, n_kv_heads=1, head_dim=16,
                       d_ff=96, vocab=50, tie_embeddings=True,
                       dtype="float32")


def test_kernel_phase_tiny():
    cases = chip_smoke.kernel_cases(
        tiny_model_cfg("attn"), _drafter_cfg(), tiny_model_cfg("ssm"),
        max_len=64, tree=8, page=16, batch=2)
    results = chip_smoke.kernel_phase(cases, interpret=True)
    assert [r["kernel"].split("[")[0] for r in results] == [
        "decode_attention", "decode_attention", "tree_attention",
        "decode_attention_paged", "ssd_scan", "int8_gemv"]


def test_serving_phase_tiny():
    tcfg, dcfg = tiny_model_cfg("attn"), _drafter_cfg()
    target = (tcfg, chip_smoke.init_weights(tcfg, 0))
    drafters = [(dcfg, chip_smoke.init_weights(dcfg, 1 + i), f"node{i}")
                for i in range(2)]
    waves = chip_smoke.serving_phase(
        target, drafters, max_len=TINY_MAX_LEN, n_requests=4, prompt_len=16,
        new_tokens=8, waves=2)
    assert [w["requests"] for w in waves] == [4, 4]
    for w in waves:
        assert w["tokens"] == 4 * 8
        assert w["verifies"] > 0 and w["tokens_per_verify"] >= 1.0
        # f32 tiny models: tree verify and decode agree to f32 rounding
        assert w["near_ties"] == 0


class _FixedRunner:
    """Runner stand-in whose logits are fixed rows, one per position."""

    def __init__(self, rows):
        self.rows, self.pos, self.dropped = rows, 0, []

    def prefill_requests(self, reqs):
        return {r: (self.rows[0], 0.0) for r in reqs}

    def decode(self, rids, tokens):
        self.pos += 1
        return np.stack([self.rows[self.pos]] * len(rids)), None

    def drop(self, rid):
        self.dropped.append(rid)


@pytest.mark.parametrize("stream,near", [
    ([0, 1], 0),                    # the argmax at every position
    ([0, 2], 1),                    # second best within the tie tolerance
    ([0, 3], None),                 # a clear loser: the check must fail
])
def test_stream_check_admits_only_near_ties(stream, near):
    top = 8.0
    tie = top - chip_smoke.NEAR_TIE_REL * top / 2
    rows = [np.array([top, 0.0, 0.0, 0.0]),
            np.array([0.0, top, tie, top - 1.0])]
    runner = _FixedRunner(rows)
    if near is None:
        with pytest.raises(AssertionError):
            chip_smoke.check_streams(runner, [[1]], [stream], rid_base=7)
    else:
        assert chip_smoke.check_streams(
            runner, [[1]], [stream], rid_base=7)[0] == near
    assert runner.dropped == [7]


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
