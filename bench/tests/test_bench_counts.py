"""Work counted from `paper_mlp`'s shapes: what `mfu` and the bank-scatter
roofline divide by. The paged configuration runs the repo's 256 features,
the dense one CIFAR-10's 3,072."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import pytest  # noqa: E402

import workcount  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _config("mlp_paged_n100k")


@pytest.mark.parametrize("name,d,n_params,weights", [
    ("mlp_paged_n100k", 256, 50_698, 50_432),
    ("mlp_dense_n100", 3072, 411_146, 410_880)])
def test_paper_mlp_shapes(name, d, n_params, weights):
    c = _config(name)
    assert workcount.leaf_shapes(c) == [(d, 128), (128,), (128, 128),
                                        (128,), (128, 10), (10,)]
    assert workcount.n_params(c) == n_params == c["n_params"]
    assert workcount.matmul_weights(c) == weights


def test_train_flops_counts_active_samples(cfg):
    # 37 active clients, K=5 steps of 100 samples, 6 FLOPs a weight
    assert workcount.train_flops(cfg, 37) == 6 * 50_432 * 37 * 5 * 100
    assert workcount.train_flops(cfg, 0) == 0


def test_bank_scatter_bytes(cfg):
    row = 4 * 50_698
    # 240 active rows over 4 rounds: 3 rows each, one delta-sum row a round
    assert workcount.bank_scatter_bytes(cfg, 240, 4) == row * (3 * 240 + 4)
