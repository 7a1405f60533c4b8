"""The benchmark's arithmetic: FLOP counts held to hand counts, and the
peaks table."""

import os

import pytest

import bench_paths  # noqa: F401  (puts benchmark/ on sys.path)
from harness import flops, loader, peaks


def _config(name, base=bench_paths.BENCH):
    return loader.read_json(os.path.join(base, "configs", name + ".json"))


def _reference(name, base=bench_paths.BENCH):
    return loader.import_file(os.path.join(base, "references", name + ".py"),
                              "reference")


def test_resnet50_flops_against_a_hand_count():
    """Multiply-accumulates counted by hand from He et al. table 1 with the
    zoo's stride placement (stage output sizes 56, 28, 14, 7)."""
    macs = 112 * 112 * 7 * 7 * 3 * 64                      # stem
    cin = 64
    for hw, f1, f3, n_blocks in ((56, 64, 256, 3), (28, 128, 512, 4),
                                 (14, 256, 1024, 6), (7, 512, 2048, 3)):
        for b in range(n_blocks):
            macs += hw * hw * cin * f1                      # 1x1 reduce
            macs += hw * hw * 9 * f1 * f1                   # 3x3
            macs += hw * hw * f1 * f3                       # 1x1 expand
            if b == 0:
                macs += hw * hw * cin * f3                  # projection
            cin = f3
    macs += 2048 * 1000                                     # dense
    cfg = _config("resnet50_imagenet_bf16")
    got = flops.forward_flops_per_item(
        _reference("resnet50_imagenet_bf16").layers(cfg))
    assert got == 2.0 * macs
    # the paper's figure for the 50-layer net: 3.8e9 multiply-adds
    assert 3.8e9 <= macs <= 3.9e9
    assert flops.train_flops_per_item(
        _reference("resnet50_imagenet_bf16").layers(cfg)) == 6.0 * macs


def test_charrnn_flops_against_a_hand_count():
    cfg = _config("charrnn_textgen_lstm", bench_paths.FIXTURES)
    per_char = (2 * (47 + 256) * 4 * 256       # first LSTM: input + recurrent
                + 2 * (256 + 256) * 4 * 256    # second LSTM
                + 2 * 256 * 47)                # per-step softmax layer
    layers = _reference("charrnn_textgen_lstm",
                        bench_paths.FIXTURES).layers(cfg)
    assert flops.forward_flops_per_item(layers) == per_char == 1_693_184
    assert flops.train_flops_per_item(layers) == 3 * per_char


def test_unknown_layer_kind_and_unknown_device_are_errors():
    with pytest.raises(KeyError):
        flops.layer_flops({"kind": "attention"})
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]
