"""The numbers that decide `correct`: the program's first steps against the
plain reference (`reference/mifa.py`), fed the same batches and cohorts.

A step is one call of the compiled chunk program (`scan_chunk` rounds).
Every run computes all the numbers below; a cell compares those that its
`bench/limits/<cell>.json` gives a limit, and `correct` holds when each of
them is within it:

* ``ids_mismatch``: rounds of the compared steps whose active client ids
  (what the program's availability layer drew, and how many rows the
  program counted as active) differ from the reference's. Exact: limit 0.
* ``loss_gap``: the largest gap of a round's training loss, as a share of
  the reference's loss.
* ``grad_gap``: the memory mean the server applied at the end of the first
  step (MIFA's gradient estimate, as the optimizer gets it), by the worst
  leaf: |norm(program leaf) - norm(reference leaf)| over the larger of the
  reference leaf's norm and the median leaf's norm.
* ``dparam_gap``: the same measure of the parameters' change over all the
  compared steps.
* ``gsum_gap``: the same measure of the server memory's sum, G_sum / N,
  after the last compared step: the bank's running `g_sum` (maintained
  through the compared steps' evictions and spills), or the mean of the
  dense memory's rows.
* ``rows_gap``: the largest distance between a client's stored memory row
  and the reference's, as a share of the reference row's norm, over every
  client active in the compared steps (rows spilled to the host included).
  A row that is missing, misplaced or written for a client that was not
  active reads 1 or more.
* ``rows_wrong``: how many of those rows lie further than `ROW_WRONG` of
  their norm from the reference's. Exact: limit 0.
* ``rows_median_gap``: the median of the same distance over the rows held
  after the first step. Training amplifies rounding: one round of local
  SGD through ReLUs turns a parameter gap of 1e-7 into row gaps up to
  1e-2 on a few clients, and the compared steps' later rounds start from
  parameters that have drifted apart. The median of the first step's rows
  is the number that stays steady from seed to seed and still moves when
  the arithmetic is coarser.

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of ``grad_gap``, ``dparam_gap`` and ``gsum_gap``: they move
by round-off alone.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("ids_mismatch", "loss_gap", "grad_gap", "dparam_gap", "gsum_gap",
           "rows_gap", "rows_wrong", "rows_median_gap")
# a stored row further than this share of its norm from the reference's is
# wrong: sound runs read at most 0.08 (rounding amplified by training), a
# missing, misplaced or unwritten row reads 1 or more
ROW_WRONG = 0.5


def _norms(leaves) -> np.ndarray:
    return np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                       for x in leaves])


def leaf_norm_gap(prog, ref, keep) -> float:
    """Worst leaf of |norm(prog) - norm(ref)| / max(norm(ref), median)."""
    n_p, n_r = _norms(prog), _norms(ref)
    scale = np.maximum(n_r, np.median(n_r))
    gaps = np.abs(n_p - n_r) / np.maximum(scale, 1e-30)
    return float(np.max(gaps[keep]))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers of `NUMBERS` from the program's snapshot and the
    reference's run (see `harness.drive_compared_steps` and `reference/mifa.py`'s `run`)."""
    n_rounds = len(ref["losses"])
    mismatch = 0
    for t in range(n_rounds):
        ids_p = prog["ids"].get(t)
        same = (ids_p is not None and np.array_equal(ids_p, ref["ids"][t])
                and prog["n_active"][t] == len(ref["ids"][t]))
        mismatch += not same
    lp = np.asarray(prog["losses"][:n_rounds], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.maximum(np.abs(lr),
                                                         1e-30)))
    g_ref = _norms(ref["mean_g"])
    keep = g_ref >= 1e-3 * np.median(g_ref)
    grad_gap = leaf_norm_gap(prog["mean_g"], ref["mean_g"], keep)
    d_prog = [np.asarray(a, np.float64) - b for a, b in
              zip(prog["params"], prog["params0"])]
    d_ref = [np.asarray(a, np.float64) - b for a, b in
             zip(ref["params"], prog["params0"])]
    dparam_gap = leaf_norm_gap(d_prog, d_ref, keep)
    gsum_gap = leaf_norm_gap(prog["mean_g_last"], ref["mean_g_last"], keep)
    gaps = _row_gaps(prog, ref)
    gaps1 = _row_gaps(prog["step1"], ref["step1"])
    return {"ids_mismatch": mismatch, "loss_gap": loss_gap,
            "grad_gap": grad_gap, "dparam_gap": dparam_gap,
            "gsum_gap": gsum_gap,
            "rows_gap": float(np.max(gaps, initial=0.0)),
            "rows_wrong": int(np.sum(gaps > ROW_WRONG)),
            "rows_median_gap": float(np.median(gaps1)) if len(gaps1)
            else 0.0}


def _row_gaps(prog: dict, ref: dict) -> np.ndarray:
    """Each reference row's distance from the program's row of the same
    client, over the reference row's norm; a client the reference touched
    and the program did not reads 1 (its row is missing), and every row
    reads at least 1 where the program holds a row for a client the
    reference never touched."""
    pos = {int(i): j for j, i in enumerate(prog["row_ids"])}
    ref_ids = [int(i) for i in ref["row_ids"]]
    extra = set(pos) - set(ref_ids)
    found = np.asarray([i in pos for i in ref_ids], bool)
    take = np.asarray([pos[i] for i in ref_ids if i in pos], np.int64)
    diff2 = np.zeros(int(found.sum()))
    norm2 = np.zeros(int(found.sum()))
    for xp, xr in zip(prog["rows"], ref["rows"]):
        r = np.asarray(xr, np.float64).reshape(len(ref_ids), -1)[found]
        p = np.asarray(xp, np.float64).reshape(len(pos), -1)[take]
        diff2 += ((p - r) ** 2).sum(1)
        norm2 += (r ** 2).sum(1)
    gaps = np.ones(len(ref_ids))
    gaps[found] = np.sqrt(diff2) / np.maximum(np.sqrt(norm2), 1e-30)
    if extra:
        gaps = np.maximum(gaps, 1.0)
    return gaps


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(every number that `limits` names within its limit,
    {name: {"value", "limit"}} for those numbers)."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS
              if k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
