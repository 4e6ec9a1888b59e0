"""Operation and byte counts against hand counts at both configurations'
published widths."""
import pytest

from bench import flops, registry, weights

STABLELM = weights.dims(registry.config("stablelm-1.6b"))
GLM = weights.dims(registry.config("glm4-9b-10l"))


def test_layer_matmul_flops_are_twice_the_layer_matrices():
    # stablelm: Q,K,V,O 4 x 2048^2 + MLP 3 x 2048 x 5632 parameters
    assert flops.layer_matmul_flops(STABLELM) == 2 * (
        4 * 2048 * 2048 + 3 * 2048 * 5632) == 102_760_448
    # glm4: Q 4096 x 4096, K,V 4096 x 256 each, O 4096^2, MLP 3 x 4096 x 13696
    assert flops.layer_matmul_flops(GLM) == 2 * (
        4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
        + 3 * 4096 * 13696) == 407_896_064


def test_attention_and_head_flops():
    # QK^T and PV: 2 x 2 x heads x head_dim per key attended
    assert flops.attn_flops(STABLELM, 1000) == 4 * 32 * 64 * 1000
    assert flops.attn_flops(GLM, 1000) == 4 * 32 * 128 * 1000
    assert flops.head_flops(GLM, 16) == 2 * 4096 * 151552 * 16
    assert flops.step_flops(GLM, 5, 3, 6, 0) == (
        5 * (3 * 407_896_064 + 4 * 32 * 128 * 6))


def test_weight_and_kv_bytes():
    # two bf16 norm gains of d per layer beside the matrices
    assert flops.layer_weight_bytes(GLM) == 2 * (203_948_032 + 2 * 4096)
    assert flops.stage_weight_bytes(GLM, 5, True) == (
        5 * 407_912_448 + 2 * (4096 * 151552 + 4096))
    assert flops.stage_weight_bytes(STABLELM, 12, False) == 12 * 2 * (
        51_380_224 + 2 * 2048)
    # K and V, kv_heads x head_dim bf16 each, per layer per token
    assert flops.kv_bytes(GLM, 5, 1000) == 5 * 2 * 2 * 2 * 128 * 1000
    assert flops.kv_bytes(STABLELM, 24, 1) == 196_608       # 192 KiB


def test_span_bytes_and_roofline():
    qo = 2 * 2 * 256 * 32 * 64
    kv = 2 * 2 * 32 * 64 * 2048
    assert flops.span_attn_bytes(STABLELM, 12, 256, 2048) == 12 * (qo + kv)
    assert flops.roofline_s(1.97e12, 819e6, 197e12, 819e9) == \
        pytest.approx((0.01, "compute"))
    t, bound = flops.roofline_s(1e9, 819e9, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_parameter_counts_match_the_configurations():
    def params(m):
        return (m["layers"] * flops.layer_weight_bytes(m) // 2
                + 2 * m["d"] * m["vocab"] + m["d"])
    # 24 x (51,380,224 + 4,096) + 2 x 2048 x 100352 + 2048
    assert params(STABLELM) == 1_644_267_520
    # 10 x (203,948,032 + 8,192) + 2 x 4096 x 151552 + 4096
    assert params(GLM) == 3_281_080_320
