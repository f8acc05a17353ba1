"""Plain MIFA (paper Algorithm 1), written from the paper.

It imports nothing of the program. Each round: the round's active clients
(drawn by the traffic's availability module, `bench/availability/`) run K
steps of SGD on their own minibatches (the traffic's dataset module,
`bench/datasets/`) from the server's weights w_t; each active client's
memory row becomes the sum of its K gradients; the server moves by the mean
of all N rows, w_{t+1} = w_t - eta_t * (1/N) sum_i G_i. The model's loss
and shapes come from the configuration's model module (`bench/models/`).
The local updates run on the device in float32, one fixed-size block of
clients per call; the memory rows are kept on the host, and their sum in
float64.

`mode` picks the arithmetic of every matrix product, forward and backward:
``"highest"`` (float32, `Precision.HIGHEST`: what the configurations state),
``"bf16x3"`` (three bfloat16 passes, as `Precision.HIGH` computes them: the
control that `correct` must refuse), or ``"half_batch"`` (highest, with each
local step's loss taken over the first half of its minibatch: a fault the
comparison must refuse).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import workload

BLOCK = 128     # clients per local-update call: one compiled shape


# --------------------------------------------------------------------------- #
# matrix products
# --------------------------------------------------------------------------- #

def _mm_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _bf16x3(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = partial(jnp.dot, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.custom_vjp
def _mm_bf16x3(a, b):
    return _bf16x3(a, b)


def _mm_bf16x3_fwd(a, b):
    return _bf16x3(a, b), (a, b)


def _mm_bf16x3_bwd(res, g):
    a, b = res
    return _bf16x3(g, b.T), _bf16x3(a.T, g)


_mm_bf16x3.defvjp(_mm_bf16x3_fwd, _mm_bf16x3_bwd)

MODES = {"highest": (_mm_highest, False), "bf16x3": (_mm_bf16x3, False),
         "half_batch": (_mm_highest, True)}


# --------------------------------------------------------------------------- #
# local update
# --------------------------------------------------------------------------- #

def make_local_update(loss, k_steps: int, weight_decay: float, mode: str):
    """Jitted (params, x (B, K, mb, ...), y (B, K, mb), eta) ->
    (G: the sum of each client's K gradients, (B, ...); mean loss (B,)),
    for a model's `loss(params, x, y, mm)`."""
    mm, half = MODES[mode]
    grad = jax.value_and_grad(loss)

    def one(params, x, y, eta):
        w = params
        acc = jax.tree.map(jnp.zeros_like, params)
        losses = []
        for k in range(k_steps):
            xk, yk = x[k], y[k]
            if half:
                xk, yk = xk[:xk.shape[0] // 2], yk[:yk.shape[0] // 2]
            loss_k, g = grad(w, xk, yk, mm)
            g = jax.tree.map(lambda gg, ww: gg + weight_decay * ww, g, w)
            w = jax.tree.map(lambda ww, gg: ww - eta * gg, w, g)
            acc = jax.tree.map(jnp.add, acc, g)
            losses.append(loss_k)
        return acc, jnp.mean(jnp.stack(losses))

    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, None)))


# --------------------------------------------------------------------------- #
# learning rates, as the configurations state them
# --------------------------------------------------------------------------- #

def learning_rates(cfg: dict, t: int) -> tuple[np.float32, np.float32]:
    """(eta_local, eta_server) of round t; schedules count from 1."""
    eta = workload.schedule(cfg)(t + 1)
    loc = eta if cfg["eta_local"] is None else cfg["eta_local"]
    return np.float32(loc), np.float32(eta)


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #

def run(cfg: dict, data, av, seed: int, params0, n_rounds: int, *,
        mode: str = "highest", snap_at: int | None = None) -> dict:
    """`n_rounds` rounds of MIFA from `params0` (the leaves of the model's
    `init_params` tree, as NumPy arrays).

    Returns the per-round `losses` and active `ids`; the memory mean the
    server applied at round `snap_at` (`mean_g`, float64 leaves) and the
    rows held then (`step1`: `row_ids`, `rows`); the memory mean after the
    last round (`mean_g_last`: G_sum / N); the final `params`, and the
    final rows of every client ever active (`row_ids`, sorted, and `rows`:
    one (len(row_ids), ...) float32 array per leaf).
    """
    n = cfg["n_clients"]
    model = workload.model(cfg)
    law = workload.plugin("availability", av.kind)
    leaves0 = list(params0)
    treedef = jax.tree.structure(jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0)))
    shapes = [leaf.shape for leaf in leaves0]
    sizes = [int(np.prod(s)) for s in shapes]
    update = make_local_update(model.loss, cfg["k_steps"],
                               cfg["weight_decay"], mode)
    stream = law.new_stream(av, seed)
    w = [np.asarray(leaf, np.float32) for leaf in leaves0]
    rows: dict[int, np.ndarray] = {}
    g_sum = np.zeros(sum(sizes), np.float64)
    out = {"losses": [], "ids": [], "mean_g": None}
    with jax.default_matmul_precision("highest"):
        for t in range(n_rounds):
            ids = law.active_ids(av, t, seed, stream)
            eta_loc, eta_srv = learning_rates(cfg, t)
            params = jax.tree.unflatten(treedef, [jnp.asarray(a) for a in w])
            losses = []
            for b0 in range(0, len(ids), BLOCK):
                blk = ids[b0:b0 + BLOCK]
                pad = ((0, BLOCK - len(blk)),)
                batch = {k: np.pad(v, pad + ((0, 0),) * (v.ndim - 1))
                         for k, v in data.batches(t, blk).items()}
                g, loss = update(params, batch["x"], batch["y"], eta_loc)
                g = np.concatenate(
                    [np.asarray(leaf).reshape(BLOCK, -1)
                     for leaf in jax.tree.leaves(g)], axis=1)
                for j, i in enumerate(blk):
                    old = rows.get(int(i))
                    g_sum += g[j].astype(np.float64)
                    if old is not None:
                        g_sum -= old
                    rows[int(i)] = g[j]
                losses.append(np.asarray(loss, np.float64)[:len(blk)])
            loss_t = np.concatenate(losses) if losses else np.zeros(0)
            out["losses"].append(float(loss_t.mean()) if len(loss_t)
                                 else 0.0)
            out["ids"].append(ids)
            mean_g = g_sum / n
            if t == snap_at:
                out["mean_g"] = _unflat(mean_g, shapes)
                out["step1"] = _stack_rows(rows, shapes)
            step = _split_flat(np.float64(eta_srv) * mean_g, sizes)
            w = [(wl - s.reshape(wl.shape)).astype(np.float32)
                 for wl, s in zip(w, step)]
    out["params"] = w
    out["mean_g_last"] = _unflat(g_sum / n, shapes)
    out.update(_stack_rows(rows, shapes))
    return out


def _stack_rows(rows: dict, shapes) -> dict:
    """{"row_ids": sorted ids, "rows": one (len(ids), ...) array a leaf}."""
    sizes = [int(np.prod(s)) for s in shapes]
    row_ids = np.asarray(sorted(rows), np.int64)
    stacked = (np.stack([rows[i] for i in row_ids]) if len(row_ids)
               else np.zeros((0, sum(sizes)), np.float32))
    return {"row_ids": row_ids,
            "rows": [blk.reshape((len(row_ids),) + s) for blk, s in zip(
                _split_flat(stacked, sizes, axis=1), shapes)]}


def _split_flat(x, sizes, axis=0):
    return np.split(x, np.cumsum(sizes)[:-1], axis=axis)


def _unflat(x, shapes):
    return [blk.reshape(s) for blk, s in zip(
        _split_flat(x, [int(np.prod(s)) for s in shapes]), shapes)]
