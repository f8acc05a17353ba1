import numpy as np
import pytest

from repro.data import ClientBatcher, TokenBatcher, label_skew_partition, \
    make_classification


def test_label_skew_two_classes_equal_sizes():
    X, y = make_classification(10, 16, 200, seed=0)
    idx, labels = label_skew_partition(y, n_clients=100, seed=0)
    sizes = [len(i) for i in idx]
    assert max(sizes) - min(sizes) <= 2  # equal up to shard rounding
    for i, ci in enumerate(idx):
        assert len(np.unique(y[ci])) <= 2
        assert set(np.unique(y[ci])) <= set(labels[i])
    # every sample assigned exactly once
    allidx = np.concatenate(idx)
    assert len(allidx) == len(y)
    assert len(np.unique(allidx)) == len(y)


def test_client_batcher_deterministic():
    X, y = make_classification(4, 8, 50, seed=0)
    idx, _ = label_skew_partition(y, n_clients=10, seed=0)
    b1 = ClientBatcher(X, y, idx, batch_size=4, k_steps=3, seed=5)
    b2 = ClientBatcher(X, y, idx, batch_size=4, k_steps=3, seed=5)
    r1, r2 = b1.sample_round(7), b2.sample_round(7)
    np.testing.assert_array_equal(r1["x"], r2["x"])
    np.testing.assert_array_equal(r1["y"], r2["y"])
    assert r1["x"].shape == (10, 3, 4, 8)
    # different rounds differ
    r3 = b1.sample_round(8)
    assert not np.array_equal(r1["x"], r3["x"])


def test_client_batches_come_from_client_data():
    X, y = make_classification(4, 8, 50, seed=0)
    idx, labels = label_skew_partition(y, n_clients=10, seed=0)
    b = ClientBatcher(X, y, idx, batch_size=8, k_steps=2, seed=0)
    r = b.sample_round(0)
    for i in range(10):
        assert set(np.unique(r["y"][i])) <= set(labels[i])


@pytest.mark.parametrize("ids", [None, [3, 0, 7], [2, 5, 0, 0]],
                         ids=["full", "cohort", "cohort_pads"])
def test_row_table_at_drawn_rows_is_the_round(ids):
    """`rows()` taken at `sample_round_rows` is `sample_round`, for the
    full round and for cohorts (pad slots drawing client 0's rows)."""
    X, y = make_classification(4, 8, 50, seed=0)
    idx, _ = label_skew_partition(y, n_clients=10, seed=0)
    b = ClientBatcher(X, y, idx, batch_size=4, k_steps=3, seed=5)
    rows, got = b.rows(), b.sample_round_rows(2, ids)
    want = b.sample_round(2, client_ids=ids)
    assert got.dtype == np.int32
    assert got.shape == (10 if ids is None else len(ids), 3, 4)
    for k in ("x", "y"):
        assert rows[k][got].dtype == want[k].dtype
        np.testing.assert_array_equal(rows[k][got], want[k])


@pytest.mark.parametrize("seed,t", [(0, 0), (5, 7), (123, 40)])
def test_client_batcher_draws_match_per_client_loop(seed, t):
    """The rows drawn are those of a loop over clients, each picking
    `default_rng((seed, t, i))` integers from its own rows (features
    narrowed to fp32)."""
    X, y = make_classification(4, 8, 50, seed=0)
    idx, _ = label_skew_partition(y, n_clients=10, seed=0)
    b = ClientBatcher(X, y, idx, batch_size=4, k_steps=3, seed=seed)
    r = b.sample_round(t)
    for i, rows in enumerate(idx):
        pick = np.random.default_rng((seed, t, i)).integers(
            0, len(rows), size=(3, 4))
        np.testing.assert_array_equal(r["x"][i],
                                      X[rows][pick].astype(np.float32))
        np.testing.assert_array_equal(r["y"][i], y[rows][pick])


def test_token_batcher_shapes_and_skew():
    tb = TokenBatcher(n_clients=4, vocab=128, seq_len=16, batch_size=2,
                      k_steps=2, stream_len=2048, seed=0)
    r = tb.sample_round(0)
    assert r["tokens"].shape == (4, 2, 2, 16)
    assert r["tokens"].max() < 128
    # non-iid: different clients use shifted vocabularies
    m0 = np.bincount(r["tokens"][0].ravel(), minlength=128).argmax()
    m3 = np.bincount(r["tokens"][3].ravel(), minlength=128).argmax()
    assert m0 != m3


def test_classification_train_test_same_distribution():
    Xtr, ytr = make_classification(4, 8, 100, seed=0)
    Xte, yte = make_classification(4, 8, 100, seed=9)
    # class means should align across splits (shared prototypes)
    for c in range(4):
        mtr = Xtr[ytr == c].mean(0)
        mte = Xte[yte == c].mean(0)
        assert np.linalg.norm(mtr - mte) < 0.5 * np.linalg.norm(mtr) + 0.5
