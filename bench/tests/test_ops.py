import importlib.util
from pathlib import Path

import pytest

OPS = Path(__file__).resolve().parents[1] / "ops"


def load(name):
    spec = importlib.util.spec_from_file_location(f"op_{name}",
                                                  OPS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_writhe_counts_by_hand():
    flops, nbytes = load("writhe").cost(2000, 256)
    pairs = 2000 * 255 * 255
    assert flops == 227 * pairs
    assert nbytes == 4 * pairs + 4 * 3 * 256 * 2000
    assert load("writhe").cost(1, 2) == (227.0, 4.0 + 24.0)


def test_flash_decode_counts_by_hand():
    # 72 pages of 64 positions, 32 heads of 64, bf16, 12 slots
    flops, nbytes = load("flash_decode").cost(72, 12, 32, 32, 64, 64)
    positions = 72 * 64
    assert nbytes == 2 * positions * 32 * 64 * 2 + 2 * 12 * 32 * 64 * 2
    assert flops == 4 * positions * 32 * 64
    # grouped heads read the same keys: bytes follow the kv heads only
    f2, b2 = load("flash_decode").cost(72, 12, 32, 8, 64, 64)
    assert b2 < nbytes and f2 == flops


def test_decoder_step_flops_stablelm():
    c = {"n_layers": 24, "d_model": 2048, "n_heads": 32, "n_kv_heads": 32,
         "head_dim": 64, "d_ff": 5632, "vocab_size": 100352}
    per_layer = 2048 * 2048 * 4 + 3 * 2048 * 5632
    weights = 24 * per_layer + 2048 * 100352
    f = load("decoder_step").flops_per_token(c, 0.0)
    assert f == 2 * weights
    assert f == pytest.approx(2.877e9, rel=1e-3)
    assert load("decoder_step").flops_per_token(c, 100.0) - f == \
        4 * 24 * 32 * 64 * 100
