"""Server learning rate eta0 * N / min(N, nominal_cohort * c) at round
clock c = t + 1: MIFA's mean runs over all N memory rows, most of them
still zero early on, so the step is rescaled to the clients seen so far.

Schedule keys: `eta0`, `nominal_cohort`.
"""


def make(spec: dict, cfg: dict):
    eta0, nominal, n = spec["eta0"], spec["nominal_cohort"], cfg["n_clients"]
    return lambda c: eta0 * n / min(n, nominal * max(c, 1))
