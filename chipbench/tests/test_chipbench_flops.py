"""Operation and byte counts against hand counts at the two
configurations' published shapes."""
import json
import os

from chipbench import flops, harness


def _model(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_parameter_counts():
    lm = flops.Dense.of(_model("mlitb-lm-100m"))
    # per layer: q and o 768x768 each, k and v 768x256 each, three
    # 768x2048 MLP matrices, two norms; tied 32768x768 embedding
    assert lm.layer_matmul_params == 1_572_864 + 4_718_592
    assert lm.params == 100_682_496 == _model("mlitb-lm-100m")["parameters"]
    qw = flops.Dense.of(_model("qwen3-4b"))
    # 2560x4096 q and o, 2560x1024 k and v, three 2560x9728, norms incl.
    # the two 128-wide q/k norms; tied 151936x2560 embedding
    assert qw.layer_matmul_params == 26_214_400 + 74_711_040
    assert qw.params == 36 * (100_925_440 + 5_376) + 388_956_160 + 2_560
    assert 2 * qw.params == 8_044_936_192          # bf16 bytes


def test_train_flops_per_token():
    lm = flops.Dense.of(_model("mlitb-lm-100m"))
    matmul = 2 * 12 * 6_291_456                 # per token, forward
    attn = 4 * 12 * 12 * 64 * 513 / 2           # mean causal keys 256.5
    head = 2 * 768 * 32_768
    assert flops.train_flops_per_token(lm, 512) == 3 * (matmul + attn + head)
    assert flops.train_flops_per_token(lm, 512) == 632_346_624


def test_serve_request_flops():
    qw = flops.Dense.of(_model("qwen3-4b"))
    # prompt of 3, 2 served tokens: 4 positions fed (keys 1+2+3+4), logits
    # at the 2 positions that chose a served token
    want = (2 * 36 * 100_925_440 * 4 + 4 * 36 * 32 * 128 * 10
            + 2 * 2560 * 151_936 * 2)
    assert flops.serve_request_flops(qw, 3, 2) == want


def test_kernel_and_step_bytes():
    n = 100_682_496
    rows = n // 128                             # 786,582 blocks
    assert flops.topk_kernel_bytes(n, 4, 4, 128) == \
        4 * 4 * (3 * n + 2 * 4 * rows) == 4_933_442_304
    assert flops.reduce_step_bytes(n, 4) == 4 * (12 * n + 4 * n + n)
    qw = flops.Dense.of(_model("qwen3-4b"))
    assert flops.decode_bytes(qw, 1000, 32) == \
        36 * (2 * 1000 * 8 * 128 * 2 + 2 * 32 * 32 * 128 * 2)
