"""Model FLOPs of a training step against hand counts."""
import json
import math

import pytest

from benchmarks.chip.families import dense_lm
from benchmarks.chip.tests.bench_tiny import ROOT


def config(name):
    return json.loads((ROOT / "benchmarks" / "chip" / "configs" /
                       f"{name}.json").read_text())


def hand_count(L, M, N, K, D, F, V, B, S):
    # per token, 2 FLOPs per weight of every matmul: q, k, v, o, three MLP
    # matrices per layer, and the tied LM head; forward + backward = 3x
    matmul = 6 * (L * (M * N * D + 2 * M * K * D + N * D * M + 3 * M * F)
                  + V * M) * B * S
    # causal attention: q.k and p.v over the S^2/2 pairs a row needs, 3x
    attention = 3 * L * B * 2 * S * S * N * D
    return matmul + attention


def test_qwen_cell_is_12_tflop_per_step():
    got = dense_lm.step_flops(config("qwen1.5-0.5b"), 4, 1024)
    assert got == hand_count(24, 1024, 16, 16, 64, 2816, 151936, 4, 1024)
    assert got / 1e12 == pytest.approx(12.02, abs=0.01)


def test_phi_cell_is_91_tflop_per_step():
    got = dense_lm.step_flops(config("phi4-mini-3.8b-L12"), 8, 1024)
    assert got == hand_count(12, 3072, 24, 8, 128, 8192, 200064, 8, 1024)
    assert got / 1e12 == pytest.approx(91.44, abs=0.01)


def test_sixteen_phi_layers_are_112_tflop_per_step():
    c = dict(config("phi4-mini-3.8b-L12"), num_hidden_layers=16)
    assert dense_lm.step_flops(c, 8, 1024) / 1e12 == pytest.approx(
        111.85, abs=0.01)


@pytest.mark.parametrize("name,want", [
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816 + 3 x 1024 biases + 2 x 1024 norms)
    # + 151936 x 1024 + 1024
    ("qwen1.5-0.5b", 24 * (4 * 1024**2 + 3 * 1024 * 2816 + 5 * 1024)
     + 151936 * 1024 + 1024),
    # 12 x (2 x 3072^2 + 2 x 3072 x 1024 + 3 x 3072 x 8192 + 2 x 3072)
    # + 200064 x 3072 + 3072: 1.82B
    ("phi4-mini-3.8b-L12", 12 * (2 * 3072**2 + 2 * 3072 * 1024
                                 + 3 * 3072 * 8192 + 2 * 3072)
     + 200064 * 3072 + 3072),
])
def test_parameters_of_the_configurations(name, want):
    specs = dense_lm.weight_specs(config(name))
    assert sum(math.prod(s) for s, _, _ in specs.values()) == want
