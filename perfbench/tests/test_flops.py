"""flops.py against numbers worked by hand for these widths."""

import pytest

import flops
import lib


def model(name):
    return lib.read_json(f"{lib.BENCH}/configs/{name}.json")


def test_train_step():
    m = model("mistral-7b-v0.3.train-1chip")
    # a layer: q 4096x4096, k and v 4096x1024 each, o 4096x4096,
    # three MLP matrices 4096x14336 = 218,103,808; head 4096x32768
    assert flops.matmul_params(m) == 2 * 218_103_808 + 134_217_728
    assert flops.total_params(m) == 704_663_552  # the issue's 704.6 M
    step = flops.train_step_flops(m, rows=2, seq=2048)
    assert step["matmul"] == pytest.approx(14.02e12, rel=1e-3)  # 14.0 TFLOP
    # causal attention: 6 * S^2 * H * hd a layer a row = 1.03e11
    assert step["attention"] == pytest.approx(4 * 6 * 2048 ** 2 * 4096)
    assert step["total"] == pytest.approx(14.43e12, rel=1e-3)
    # 7 score-sized matmuls of 2 * S^2/2 * H*hd each, 2 rows, 2 layers
    assert flops.flash_kernel_flops(m, 2, 2048) == pytest.approx(
        7 * 2 * 2 * 2048 ** 2 * 4096)


def test_serving_bytes():
    m = dict(model("mistral-7b-v0.3.serve-1chip"), num_hidden_layers=16)
    assert flops.kv_bytes_per_token(m) == 64 * 1024  # 64 KiB at 16 layers
    assert flops.kv_bytes_per_token(dict(m, num_hidden_layers=1)) == 4096
    need = flops.paged_decode_needs(m, live_tokens=1000, live_slots=10)
    assert need["bytes"] == 1000 * 65536 + 2 * 10 * 4096 * 2 * 16
    assert need["flops"] == 4 * 1000 * 4096 * 16


def test_roofline_says_which_peak_bounds():
    peaks = lib.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    r = flops.roofline_seconds(197e12, 1.0, peaks)
    assert r == {"seconds": pytest.approx(1.0), "bound": "flops"}
    r = flops.roofline_seconds(1.0, 819e9 * 2, peaks)
    assert r == {"seconds": pytest.approx(2.0), "bound": "bytes"}
    with pytest.raises(RuntimeError):
        lib.peaks_for("some other chip")
