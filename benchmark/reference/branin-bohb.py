"""Plain reference of ``branin-bohb``: the objective written out again.

Every loss the window reported is held against Branin plus the fidelity
term the configuration states, in float64 numpy on the reported
hyperparameters and budget. Imports nothing of the program.
"""

import numpy as np

#: widest |reported - reference| / (1 + |reference|) over every evaluation
#: of the window. Readings it was set from (PERF.md section 2): float32
#: rounding of x, x**2 at values up to 300 and a sine argument up to 250
#: gives some 1e-5; the bfloat16 control reads above 1e-1.
LOSS_GAP_LIMIT = 1e-3


def objective(x, y, budget, xp=np, dtype=np.float64):
    x, y, budget = (xp.asarray(a, dtype) for a in (x, y, budget))
    b, c, t = 5.1 / (4 * np.pi ** 2), 5.0 / np.pi, 1.0 / (8 * np.pi)
    value = (y - b * x ** 2 + c * x - 6.0) ** 2 + 10.0 * (1 - t) * xp.cos(x) + 10.0
    return value + 5.0 * xp.sin(13.7 * x + 7.3 * y) / xp.sqrt(budget + 1e-9)


def compare(config, traffic, records, seed, control=False):
    """``[(name, value, limit)]``. With ``control`` the reference computed
    in bfloat16 stands in the program's place."""
    gap = 0.0
    for rec in records:
        x, y = rec["config"]["x"], rec["config"]["y"]
        want = objective(x, y, rec["budget"])
        got = rec["loss"]
        if control:
            import jax.numpy as jnp

            got = np.asarray(
                objective(x, y, rec["budget"], jnp, jnp.bfloat16), np.float64)
        # a crashed evaluation has no loss to compare; Branin never crashes,
        # so one counts as the widest gap there is
        row = np.where(np.isnan(got), np.inf, np.abs(got - want) / (1 + np.abs(want)))
        gap = max(gap, float(row.max()))
    return [("loss_gap_widest", gap, LOSS_GAP_LIMIT)]
