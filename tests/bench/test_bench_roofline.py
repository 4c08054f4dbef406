"""Operation and byte counts of both configurations against hand counts."""
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import pytest

from bench import roofline


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


QWEN = config("qwen1.5-4b_qwen2-0.5bx2")["target"]
DANUBE = config("danube3-4b_danube3-500mx4")["target"]


def test_qwen_layer_and_weights_by_hand():
    # q,k,v,o 4 x 2560 x 2560; biases 3 x 2560; MLP 3 x 2560 x 6912; norms
    layer = 4 * 2560 * 2560 + 3 * 2560 + 3 * 2560 * 6912 + 2 * 2560
    assert roofline.layer_params(QWEN) == layer == 79_311_360
    # 40 layers, final norm, head over the published 151936 rows, bf16
    assert roofline.weight_bytes(QWEN) == 2 * (40 * layer + 2560
                                               + 2560 * 151936)


def test_danube_layer_by_hand():
    # q,o 3840 x 3840 each; k,v 3840 x 960 each (8 heads x 120); no bias
    layer = 2 * 3840 * 3840 + 2 * 3840 * 960 + 3 * 3840 * 10240 + 2 * 3840
    assert roofline.layer_params(DANUBE) == layer


@pytest.mark.parametrize("m,ctx", [(QWEN, 0), (QWEN, 511), (DANUBE, 100)])
def test_token_flops_by_hand(m, ctx):
    d, hq, hkv, hd, ff, L, V = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                                m["head_dim"], m["d_ff"], m["n_layers"],
                                m["vocab"])
    per_layer = (2 * d * hq * hd * 2 + 2 * d * hkv * hd * 2 + 2 * 3 * d * ff
                 + 2 * 2 * hq * hd * (ctx + 1))
    assert roofline.token_flops(m, ctx) == L * per_layer + 2 * d * V


def test_least_time_picks_the_binding_roof():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.call_least_seconds(QWEN, [100] * 4, peak)
    assert bound == "memory"
    assert t == pytest.approx(roofline.weight_bytes(QWEN) / 819e9)
    t, bound = roofline.call_least_seconds(QWEN, [100] * 4096, peak)
    assert bound == "compute"


def test_missing_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
