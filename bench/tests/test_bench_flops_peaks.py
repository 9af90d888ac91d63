"""FLOPs per token against the closed form from the published widths, and
the peak table's refusal of an unknown chip."""
import json
import os

import pytest

import flops
import peaks

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def load(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,d,h,kv,f,v,layers,params", [
    ("yi-6b.2l", 4096, 32, 4, 11008, 64000, 2, 870_338_560),
    ("yi-6b.8l", 4096, 32, 4, 11008, 64000, 8, 1_908_477_952),
    ("qwen2.5-7b.7l", 3584, 28, 4, 18944, 152064, 7, 2_721_402_880),
])
def test_flops_per_token_closed_form(name, d, h, kv, f, v, layers, params):
    c = load(name)
    dims = flops.Dims.from_config(c)
    hd = d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    ctx = 100
    want = 2 * per_layer * layers + 4 * h * hd * layers * ctx + 2 * d * v
    assert flops.decode_token_flops(dims, ctx) == want
    assert flops.parameters(dims, c.get("attention_bias", False)) == params


def test_grpo_sample_is_six_forwards():
    dims = flops.Dims.from_config(load("yi-6b.2l"))
    p, r = 40, 64
    fwd = (flops.layer_flops(dims, p + r, (p + r) * (p + r + 1) // 2)
           + flops.head_flops(dims, r))
    assert flops.grpo_sample_flops(dims, p, r) == 6 * fwd


def test_paged_attention_cost_counts_live_rows():
    dims = flops.Dims.from_config(load("qwen2.5-7b.7l"))
    f, b = flops.paged_attention_cost(dims, [10, 30])
    assert f == 4 * 28 * 128 * 7 * 40
    assert b == (2 * 4 * 128 * 2 * 7 * 40) + (2 * 28 * 128 * 2 * 7 * 2)


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
