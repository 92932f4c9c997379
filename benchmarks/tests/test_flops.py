"""Operations and bytes from shapes, each case beside the function it tests:
the families' own counts and ``harness/flops.py``'s cache rows."""

import pytest

from benchmarks.harness import flops, manifest

resnet_train = manifest.family("resnet", "train")
transformer_serve = manifest.family("transformer", "serve")

RESNET50 = {"num_classes": 1000, "stage_sizes": (3, 4, 6, 3), "width": 64}
CGPT = {"vocab_size": 50304, "dim": 2048, "n_layers": 24, "n_heads": 16,
        "mlp_ratio": 4, "max_seq_len": 2048}


def test_resnet50_forward_is_about_4_1_gmac():
    # He et al. quote 3.8e9 for v1; v1.5 moves the stride to the 3x3: ~4.1e9.
    macs = resnet_train.forward_macs(RESNET50, 224)
    assert macs == pytest.approx(4.09e9, rel=0.01)
    # By hand: the stem is 112*112*49*3*64, the head 2048*1000.
    assert resnet_train.forward_macs(
        {"num_classes": 1000, "stage_sizes": (), "width": 64}, 224
    ) == 112 * 112 * 49 * 3 * 64 + 64 * 1000
    assert resnet_train.train_flops_per_example(
        {"program": RESNET50}, {"data": {"image_size": 224}}) == 6 * macs


@pytest.mark.parametrize("params,width", [("float32", 4), ("bfloat16", 2)])
def test_decode_step_bytes_by_hand(params, width):
    D, H, V, L = 2048, 8192, 50304, 24
    block = 3 * D * D + D * D + 2 * D * H + H + D + 4 * D
    n_params = L * block + 2 * D + D * V + 2 * 8 * D
    rows = 1000.0
    want = n_params * width + rows * L * 2 * D * 2
    config = {"program": CGPT, "precision": {"params": params}}
    assert transformer_serve.decode_step_bytes(config, slots=8, cache_rows=rows) == want
    # About 5.25 GB of float32 parameters: 6.4 ms at 819 GB/s.
    assert 5.2e9 < n_params * 4 < 5.3e9


def test_mean_cache_rows_from_stamps():
    # One session, prompt 10, tokens every 0.1 s from t=2.0: at t in [2, 3)
    # it holds 10 + (t - 2) / 0.1 rows; before 2.0 it feeds one row a step.
    req = [{"id": 0, "prompt": list(range(10))}]
    rec = [{"id": 0, "times": [2.0 + 0.1 * j for j in range(11)]}]
    assert flops.mean_cache_rows(rec, req, 2.0, 3.0) == pytest.approx(15.0, abs=0.01)
    assert flops.mean_cache_rows(rec, req, 1.0, 2.0) == pytest.approx(5.0, abs=0.01)
    assert flops.mean_cache_rows(rec, req, 5.0, 6.0) == 0.0
