"""Deterministic per-client batching for the FL round loop.

`sample_round(t)` yields a pytree whose leaves have shape (N, K, mb, ...):
one minibatch per client per local step, reproducible from (seed, t).

`sample_round(t, client_ids=ids)` yields the *compact* cohort variant —
leaves (len(ids), K, mb, ...) holding exactly the rows the full call would
have produced for those clients (same (seed, t, i) streams), in `ids` order.
The cohort round path (core.runner / repro.bank) lives on this: batch
assembly is O(|A|), never O(N). `ProceduralBatcher` pushes that to the data
itself — client shards are regenerated from (seed, client) on demand, so
million-client runs hold no per-client state at all.
"""
from __future__ import annotations

import numpy as np

from repro.data.synthetic import make_token_stream


class ClientBatcher:
    """Tabular classification batches: {'x': (N,K,mb,dim), 'y': (N,K,mb)}.

    Two surfaces over one row table: `rows()` holds every client's rows
    back to back (client order, `offsets[i]` the first row of client i) and
    `sample_round_rows(t)` draws a round's rows of that table, so a scan
    program can keep the table on the device and gather each round there
    from indices alone; `sample_round(t)` is the host gather of the same
    rows at the same indices.
    """

    def __init__(self, X: np.ndarray, y: np.ndarray,
                 client_indices: list[np.ndarray], *, batch_size: int,
                 k_steps: int, seed: int = 0):
        self.offsets = np.cumsum([0] + [len(i) for i in client_indices])[:-1]
        order = np.concatenate(client_indices)
        self._rows = {"x": np.asarray(X[order], np.float32),
                      "y": np.asarray(y[order], np.int32)}
        # per-client views of the table
        self.Xs = np.split(self._rows["x"], self.offsets[1:])
        self.ys = np.split(self._rows["y"], self.offsets[1:])
        self.n_clients = len(client_indices)
        self.batch_size = batch_size
        self.k_steps = k_steps
        self.seed = seed
        self.dim = X.shape[1]

    def rows(self) -> dict:
        """Every client's rows in client order: {'x': (R, dim) f32,
        'y': (R,) int32}."""
        return self._rows

    def sample_round_rows(self, t: int, client_ids=None) -> np.ndarray:
        """(len(ids), K, mb) int32 rows of `rows()` for round t: client i's
        picks come from `default_rng((seed, t, i))`, offset to its rows."""
        mb, K = self.batch_size, self.k_steps
        ids = (np.arange(self.n_clients) if client_ids is None
               else np.asarray(client_ids, np.int64))
        out = np.empty((len(ids), K, mb), np.int32)
        for j, i in enumerate(ids):
            i = int(i)
            rng = np.random.default_rng((self.seed, t, i))
            out[j] = self.offsets[i] + rng.integers(0, len(self.ys[i]),
                                                    size=(K, mb))
        return out

    def sample_round(self, t: int, client_ids=None) -> dict:
        idx = self.sample_round_rows(t, client_ids)
        return {k: np.take(v, idx, axis=0) for k, v in self._rows.items()}


class TokenBatcher:
    """LM batches {'tokens': (N,K,mb,seq)} from per-client synthetic streams."""

    def __init__(self, *, n_clients: int, vocab: int, seq_len: int,
                 batch_size: int, k_steps: int, stream_len: int = 1 << 16,
                 seed: int = 0):
        self.streams = [
            make_token_stream(vocab, stream_len, seed=seed + i,
                              client_shift=i * (vocab // max(n_clients, 1)))
            for i in range(n_clients)]
        self.n_clients = n_clients
        self.seq_len = seq_len
        self.batch_size = batch_size
        self.k_steps = k_steps
        self.seed = seed

    def sample_round(self, t: int, client_ids=None) -> dict:
        mb, K, S = self.batch_size, self.k_steps, self.seq_len
        ids = (np.arange(self.n_clients) if client_ids is None
               else np.asarray(client_ids, np.int64))
        out = np.empty((len(ids), K, mb, S), np.int32)
        for j, i in enumerate(ids):
            i = int(i)
            rng = np.random.default_rng((self.seed, t, i, 7))
            starts = rng.integers(0, len(self.streams[i]) - S - 1, size=(K, mb))
            for k in range(K):
                for b in range(mb):
                    s = starts[k, b]
                    out[j, k, b] = self.streams[i][s:s + S]
        return {"tokens": out}


class ProceduralBatcher:
    """Stateless tabular batches for million-client cohort runs.

    No per-client storage: client i's shard is an infinite stream defined by
    (seed, i) — features are a client-specific mean shift (non-iid, label-
    correlated like data.partition's label skew) plus noise, labels come from
    a fixed random linear teacher. Identical draws whether a client is
    sampled via the full path or a compact cohort, so ProceduralBatcher is a
    drop-in for ClientBatcher at any N.
    """

    def __init__(self, *, n_clients: int, dim: int, n_classes: int = 2,
                 batch_size: int, k_steps: int, shift: float = 1.0,
                 noise: float = 1.0, seed: int = 0):
        self.n_clients = n_clients
        self.dim = dim
        self.n_classes = n_classes
        self.batch_size = batch_size
        self.k_steps = k_steps
        self.shift = shift
        self.noise = noise
        self.seed = seed
        teacher_rng = np.random.default_rng((seed, 0x7EAC))
        self.teacher = teacher_rng.normal(size=(dim, n_classes)) \
            .astype(np.float32)

    def _client_mean(self, i: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, 0xC11E27, i))
        return (self.shift * rng.normal(size=self.dim)).astype(np.float32)

    def sample_round(self, t: int, client_ids=None) -> dict:
        mb, K = self.batch_size, self.k_steps
        ids = (np.arange(self.n_clients) if client_ids is None
               else np.asarray(client_ids, np.int64))
        xs = np.empty((len(ids), K, mb, self.dim), np.float32)
        ys = np.empty((len(ids), K, mb), np.int32)
        for j, i in enumerate(ids):
            i = int(i)
            rng = np.random.default_rng((self.seed, t, i))
            x = rng.normal(size=(K, mb, self.dim)).astype(np.float32) \
                * self.noise + self._client_mean(i)
            xs[j] = x
            ys[j] = np.argmax(x @ self.teacher, axis=-1).astype(np.int32)
        return {"x": xs, "y": ys}


class JitProceduralBatcher:
    """Procedural batches with a jit-native drawing surface (two surfaces,
    like `repro.scenarios` / `repro.sim.latency`).

    `ProceduralBatcher` regenerates client shards on demand but assembles
    each round with a Python loop over clients — O(N) host work per round,
    which dominates at N=10⁵⁺. This batcher draws the SAME kind of data
    (client-specific mean shifts + noise, labels from a fixed random linear
    teacher — different RNG streams, so draws are not bitwise equal to
    `ProceduralBatcher`'s) from `jax.random` counter streams instead:

      * `batch_fn()` returns a pure ``(t) -> {'x', 'y'}`` function drawing
        the whole round IN-program (keyed by fold_in, so round t's batch
        depends only on (seed, t)) — the compiled simulator's scan body
        calls it so no (L, N, ...) batch stack ever crosses the host.
      * `sample_round(t)` materialises the jitted surface to NumPy —
        bit-identical to the in-program draw, so loop/heap drivers see the
        same data as compiled ones.

    `eval_batch(n)` draws a held-out set (its own stream, shared by every
    round) for time-to-accuracy eval functions.
    """

    def __init__(self, *, n_clients: int, dim: int, n_classes: int = 2,
                 batch_size: int, k_steps: int, shift: float = 1.0,
                 noise: float = 1.0, seed: int = 0):
        import jax
        self.n_clients = n_clients
        self.dim = dim
        self.n_classes = n_classes
        self.batch_size = batch_size
        self.k_steps = k_steps
        self.shift = shift
        self.noise = noise
        self.seed = seed
        kt, km, kd, ke = jax.random.split(jax.random.PRNGKey(seed), 4)
        self._k_teacher, self._k_means = kt, km
        self._k_data, self._k_eval = kd, ke
        self._host_fn = None

    def batch_fn(self):
        """Pure ``(t) -> {'x': (N, K, mb, dim) f32, 'y': (N, K, mb) i32}``,
        jit/vmap/scan-safe; all draws keyed by fold_in(seed-derived keys, t)."""
        import jax
        import jax.numpy as jnp
        n, k, mb, d = (self.n_clients, self.k_steps, self.batch_size,
                       self.dim)
        teacher = jax.random.normal(self._k_teacher, (d, self.n_classes),
                                    jnp.float32)
        means = self.shift * jax.random.normal(self._k_means, (n, d),
                                               jnp.float32)
        noise, k_data = jnp.float32(self.noise), self._k_data

        def draw(t):
            z = jax.random.normal(jax.random.fold_in(k_data, t),
                                  (n, k, mb, d), jnp.float32)
            x = noise * z + means[:, None, None, :]
            y = jnp.argmax(x @ teacher, axis=-1).astype(jnp.int32)
            return {"x": x, "y": y}

        return draw

    def sample_round(self, t: int, client_ids=None) -> dict:
        """Round t's batch as NumPy (the jit surface materialised — identical
        to in-program draws); `client_ids` selects a compact cohort view."""
        import jax
        if self._host_fn is None:
            self._host_fn = jax.jit(self.batch_fn())
        batch = {k: np.asarray(v) for k, v in self._host_fn(t).items()}
        if client_ids is not None:
            ids = np.asarray(client_ids, np.int64)
            batch = {k: v[ids] for k, v in batch.items()}
        return batch

    def eval_batch(self, n_eval: int = 2048) -> dict:
        """Held-out {'x': (n_eval, dim), 'y': (n_eval,)} from the eval
        stream: global mean (no client shift) + noise, teacher labels."""
        import jax
        import jax.numpy as jnp
        teacher = np.asarray(jax.random.normal(
            self._k_teacher, (self.dim, self.n_classes), jnp.float32))
        x = self.noise * np.asarray(jax.random.normal(
            self._k_eval, (n_eval, self.dim), jnp.float32))
        y = np.argmax(x @ teacher, axis=-1).astype(np.int32)
        return {"x": x, "y": y}
