"""The required-operations count against a hand count."""
from __future__ import annotations

import harness
from flops import train_flops_per_seed

SIZES = dict(feature_dim=1024, hidden=256, n_classes=47, fanouts=[25, 10])


def test_sage_training_count():
    m = harness.load_ref("sage").matmuls(SIZES)
    # layer 1: 26 rows x two 1024x256 products, forward + weight gradient;
    # layer 2: two 256x256 products and the 256x47 head, each forward,
    # weight and input gradient
    hand = (26 * 2 * 2 * 1024 * 256 * 2 + 2 * 2 * 256 * 256 * 3
            + 2 * 256 * 47 * 3)
    assert train_flops_per_seed(m) == hand == 55_384_576


def test_gcn_training_count():
    m = harness.load_ref("gcn").matmuls(SIZES)
    hand = 26 * 2 * 1024 * 256 * 2 + 2 * 256 * 256 * 3 + 2 * 256 * 47 * 3
    assert train_flops_per_seed(m) == hand

