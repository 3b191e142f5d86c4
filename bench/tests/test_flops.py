"""Hand counts of the FLOP and byte model for two configurations, and the
peaks table."""
import json
import os

import pytest

from bench import harness
from bench.flops import dense

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


# MiniCPM-2B's published widths cut to 6 layers: a second shape for the
# counts (no cell runs it yet)
MINICPM = {"hidden_size": 2304, "intermediate_size": 5760,
           "num_hidden_layers": 6, "num_attention_heads": 36,
           "num_key_value_heads": 36, "vocab_size": 122753}


def cfg(name):
    if name == "minicpm-2b-6l":
        return MINICPM
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name,matmul,total,flops,b,t", [
    # qwen3-4b-4l: 4 x (2560x6144 + 4096x2560 + 2560x19456 + 9728x2560)
    # + 2560x151936 = 792,657,920; with the embedding and the norm scales
    # 1,181,638,144; 6 x 2048 tokens x 792,657,920 + 3 x 4 x 4 x 512^2 x
    # 32 x 128 x 4 layers of attention = 9.946e12
    ("qwen3-4b-4l", 792_657_920, 1_181_638_144, 9.9463e12, 4, 512),
    # minicpm-2b-6l: 6 x (2304x6912 + 2304x2304 + 2304x11520 + 5760x2304)
    # + 2304x122753 = 649,103,616; with the embedding and the norm scales
    # 931,956,480; B=2, T=2048: 6 x 4096 x 649,103,616 + 3 x 4 x 2 x
    # 2048^2 x 36 x 64 x 6 = 1.7344e13
    ("minicpm-2b-6l", 649_103_616, 931_956_480, 1.7344e13, 2, 2048),
])
def test_hand_counts(name, matmul, total, flops, b, t):
    c = cfg(name)
    assert dense.matmul_params(c) == matmul
    assert dense.params(c) == total
    assert dense.train_step_flops(c, b, t) == pytest.approx(flops, rel=1e-4)


def test_ghost_norm_cost_and_bound():
    flops, nbytes = dense.ghost_norm_cost(4, 512, 2560, 9728)
    assert flops == 4 * 512 * 513 * (2560 + 9728 + 1)
    assert nbytes == 4 * 512 * (2560 + 9728) * 2 + 16
    peak = harness.peak_of("TPU v5 lite")
    secs, bound = dense.least_time(flops, nbytes, peak)
    assert bound == "compute" and secs == pytest.approx(flops / 197e12)
    assert dense.least_time(1.0, 1e9, peak)[1] == "memory"


def test_unknown_device_kind_is_an_error():
    assert harness.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.CellError):
        harness.peak_of("cpu")
