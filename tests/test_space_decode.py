"""``ConfigurationSpace.from_vectors`` decodes a matrix of vectors by column
and equals ``from_vector`` row for row (ISSUE 29).

The reference is the loop it replaces in the fused replay,
``dict(from_vector(row))`` once a row, kept here as the oracle: equal keys
in equal order, values equal under ``==`` and of the same Python type (the
values go into ``Result`` and the JSON logs, so never a numpy scalar).
The arithmetic is unchanged, so equality is exact and no tolerance is set.
"""

import numpy as np
import pytest

from hpbandster_tpu.space import (
    CategoricalHyperparameter,
    ConfigurationSpace,
    Constant,
    EqualsCondition,
    OrdinalHyperparameter,
    UniformFloatHyperparameter,
    UniformIntegerHyperparameter,
)
from hpbandster_tpu.space.hyperparameters import Hyperparameter

ROWS = 4096


class Halved(Hyperparameter):
    """A kind nobody specialised: it inherits the per-element call."""

    def from_unit(self, u):
        return {"half": u / 2}


KINDS = {
    "float-linear": lambda: [UniformFloatHyperparameter("x", -5.0, 10.0)],
    "float-log": lambda: [UniformFloatHyperparameter("x", 1e-4, 1.0, log=True)],
    "float-quantised": lambda: [UniformFloatHyperparameter("x", 0.0, 1.0, q=0.125)],
    "float-log-quantised": lambda: [
        UniformFloatHyperparameter("x", 0.5, 64.0, log=True, q=0.5)],
    "integer-linear": lambda: [UniformIntegerHyperparameter("x", -3, 12)],
    "integer-log-lower-1": lambda: [UniformIntegerHyperparameter("x", 1, 1000, log=True)],
    "integer-log-lower-above-1": lambda: [
        UniformIntegerHyperparameter("x", 16, 512, log=True)],
    "integer-beyond-float64": lambda: [UniformIntegerHyperparameter("x", 0, 2**60)],
    "categorical": lambda: [CategoricalHyperparameter("x", ["relu", "tanh", 3, None])],
    "ordinal": lambda: [OrdinalHyperparameter("x", [8, 16, 32, 64, 128])],
    "constant": lambda: [Constant("x", ("kept", 1))],
    "unspecialised-kind": lambda: [Halved("x")],
    "mixed": lambda: [
        UniformFloatHyperparameter("lr", 1e-4, 1.0, log=True),
        UniformIntegerHyperparameter("width", 16, 512, log=True),
        UniformFloatHyperparameter("momentum", 0.0, 0.99),
        CategoricalHyperparameter("act", ["relu", "tanh", "gelu"]),
        OrdinalHyperparameter("batch", [16, 32, 64]),
        UniformIntegerHyperparameter("layers", 1, 8),
        Constant("optimizer", "sgd"),
        UniformFloatHyperparameter("dropout", 0.0, 0.9, q=0.1),
    ],
}


def space_of(hps, condition=False):
    cs = ConfigurationSpace(seed=0)
    cs.add_hyperparameters(hps)
    if condition:
        cs.add_condition(EqualsCondition(cs.get_hyperparameter("momentum"),
                                         cs.get_hyperparameter("act"), "tanh"))
    return cs


def matrix_for(cs, seed):
    """Seeded float32 rows as the device returns them, then the edges:
    0, 1, below 0, above 1, signed zero, far outside, and for every
    dimension the unit values that decode onto a rounding boundary."""
    rng = np.random.default_rng(seed)
    hps = cs.get_hyperparameters()
    cols = []
    for hp in hps:
        if hp.vartype == "c":
            col = rng.random(ROWS)
        else:
            col = rng.integers(0, max(hp.num_choices, 1), ROWS).astype(float)
        cols.append(col)
    body = np.stack(cols, axis=1).astype(np.float32).astype(np.float64)
    edges = [0.0, 1.0, -0.25, 1.75, -0.0, 1e30, -1e30, 0.5, 1.5, 2.5, 3.5,
             np.nextafter(0.5, 0), np.nextafter(0.5, 1)]
    for hp in hps:
        n = getattr(hp, "upper", 0) - getattr(hp, "lower", 0) + 1
        if isinstance(hp, UniformIntegerHyperparameter) and not hp.log and n < 64:
            # v = lower - 0.5 + u * n lands on k + 0.5: round half to even
            edges += [k / n for k in range(1, n)]
        if getattr(hp, "q", None) and not hp.log:
            span = hp.upper - hp.lower
            edges += [(k + 0.5) * hp.q / span for k in range(int(span / hp.q))]
    tail = np.repeat(np.asarray(edges)[:, None], len(hps), axis=1)
    return np.concatenate([body, tail])


def assert_rows_equal(cs, matrix, decoded):
    assert len(decoded) == len(matrix)
    for row, got in zip(matrix, decoded):
        want = dict(cs.from_vector(row))
        assert type(got) is dict
        assert got == want, (row, got, want)
        assert list(got) == list(want)
        assert [type(v) for v in got.values()] == [type(v) for v in want.values()], (
            row, got, want)


@pytest.mark.parametrize("kind", KINDS)
def test_from_vectors_equals_from_vector_row_for_row(kind):
    cs = space_of(KINDS[kind]())
    matrix = matrix_for(cs, seed=29)
    assert cs.decodes_by_column(matrix)
    assert_rows_equal(cs, matrix, cs.from_vectors(matrix))
    # the device's dtype, and a plain nested list, decode the same
    assert cs.from_vectors(matrix.astype(np.float32)) == cs.from_vectors(
        matrix.astype(np.float32).astype(np.float64))
    assert cs.from_vectors(matrix[:3].tolist()) == cs.from_vectors(matrix[:3])


@pytest.mark.parametrize("kind", [k for k in KINDS if k != "unspecialised-kind"])
def test_from_unit_many_is_from_unit_of_every_entry(kind):
    """The contract on the hyperparameter itself, where an override
    exists; and an override exists for every kind the package ships."""
    cs = space_of(KINDS[kind]())
    matrix = matrix_for(cs, seed=7)
    for i, hp in enumerate(cs.get_hyperparameters()):
        assert type(hp).from_unit_many is not Hyperparameter.from_unit_many
        got = hp.from_unit_many(matrix[:, i])
        want = [hp.from_unit(float(u)) for u in matrix[:, i]]
        assert got == want
        assert [type(v) for v in got] == [type(v) for v in want]


@pytest.mark.parametrize("why", ["condition", "nan-entry", "no-dimension"])
def test_what_the_input_shows_sends_it_down_the_row_path(why, monkeypatch):
    """A space with a condition, a matrix with a NaN, a space with no
    dimension: every row goes through ``from_vector``, and no column is
    decoded."""
    cs = space_of([] if why == "no-dimension" else KINDS["mixed"](),
                  condition=why == "condition")
    matrix = matrix_for(cs, seed=3)[:256] if cs.dim else np.zeros((5, 0))
    if why == "nan-entry":
        matrix[17, 2] = np.nan
        matrix[40, :] = np.nan

    def never(self, column):
        raise AssertionError("a column was decoded")

    for cls in [Hyperparameter] + Hyperparameter.__subclasses__():
        monkeypatch.setattr(cls, "from_unit_many", never, raising=False)
    rows = []
    from_vector = ConfigurationSpace.from_vector
    monkeypatch.setattr(ConfigurationSpace, "from_vector",
                        lambda self, v: rows.append(1) or from_vector(self, v))
    assert not cs.decodes_by_column(matrix)
    decoded = cs.from_vectors(matrix)
    assert len(rows) == len(matrix)
    assert_rows_equal(cs, matrix, decoded)
    if why == "condition":
        assert any("momentum" not in cfg for cfg in decoded)
    if why == "nan-entry":
        assert "momentum" not in decoded[17] and decoded[40] == {}


def test_the_column_path_never_reaches_from_vector(monkeypatch):
    cs = space_of(KINDS["mixed"]())
    matrix = matrix_for(cs, seed=5)

    def never(self, vector):
        raise AssertionError("a row was decoded")

    want = [dict(cs.from_vector(row)) for row in matrix]
    monkeypatch.setattr(ConfigurationSpace, "from_vector", never)
    assert cs.from_vectors(matrix) == want
    assert cs.from_vectors(matrix[:0]) == []


@pytest.mark.parametrize("shape", [(7,), (8, 7), (8, 9), (2, 8, 8), ()])
@pytest.mark.parametrize("conditional", [False, True], ids=["flat", "conditional"])
def test_a_wrong_shape_raises_what_from_vector_raises(shape, conditional):
    cs = space_of(KINDS["mixed"](), condition=conditional)
    assert cs.dim == 8
    with pytest.raises(ValueError, match="expected shape") as one:
        cs.from_vector(np.zeros(7))
    with pytest.raises(type(one.value), match="expected shape"):
        cs.from_vectors(np.zeros(shape))
