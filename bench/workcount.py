"""Work counted from a configuration's shapes, never from the program's
compiled code: the model FLOPs of MIFA's local training and the bytes the
bank scatter needs to move. Shapes come from the configuration's model
module (`bench/models/<model>.py`)."""
from __future__ import annotations

import workload


def leaf_shapes(cfg: dict) -> list[tuple]:
    """The model's parameter shapes."""
    return workload.model(cfg).leaf_shapes(cfg)


def n_params(cfg: dict) -> int:
    """Every parameter: matrix weights and biases."""
    total = 0
    for s in leaf_shapes(cfg):
        n = 1
        for d in s:
            n *= d
        total += n
    return total


def matmul_weights(cfg: dict) -> int:
    """Weights that enter a matrix product (biases excluded)."""
    return sum(a * b for a, b in (s for s in leaf_shapes(cfg)
                                  if len(s) == 2))


def train_flops(cfg: dict, active_client_rounds: int) -> float:
    """Model FLOPs of the local training of `active_client_rounds` client
    updates: 6 FLOPs per matrix weight per sample (2 forward, 4 backward),
    K steps of `batch_size` samples each. Updates computed for clients that
    are not active in their round do no useful work and are not counted."""
    samples = active_client_rounds * cfg["k_steps"] * cfg["batch_size"]
    return 6.0 * matmul_weights(cfg) * samples


def bank_scatter_bytes(cfg: dict, active_client_rounds: int,
                       rounds: int) -> float:
    """HBM bytes the memory-row update needs over `rounds` rounds: for each
    active client, read its fresh update and its old row and write its new
    row (3 rows); for each round, write the rows' delta sum (1 row). Rows
    of fp32 parameters; pad slots of the cohort are not needed work."""
    row_bytes = 4 * n_params(cfg)
    return row_bytes * (3.0 * active_client_rounds + rounds)
