"""Server learning rate eta0 / c at round clock c = t + 1.

Schedule keys: `eta0`.
"""


def make(spec: dict, cfg: dict):
    eta0 = spec["eta0"]
    return lambda c: eta0 / max(c, 1)
