"""A bracket draws its lanes' initial weights once. What of a lane's
initial weights no configuration changes, the unit draw, is stated by the
lane's maker (``lane_facts.shared``, the first half of ``lane.Init``); a loop
that takes a bracket's evaluations in turn (``ops.fused._sh_bracket_in_turn``),
or a rung's (``eval_lanes``), makes it once before the loop where it fits
the device beside the lanes, and an evaluation scales it. Handing the draw
over changes where it is made and nothing else: the bracket reads the same
bits as one whose every evaluation makes the same tree itself. Against an
evaluation that draws and scales in one fusion, as before there were
halves, a leaf differs in its last two bits at most (the compiler folds the
normal's square root of two into the scale there). Every one of the six
lanes' makers is a case, at the size of its own tests."""

import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu import workloads
from hpbandster_tpu.ops import fused
from hpbandster_tpu.workloads import lane

from lane_names import random_bits

#: lane -> (its small configuration's module, the cell's builder, the sizes
#: of its own sweep test)
LANES = {
    "olmo_hybrid": ("olmo_hybrid_small", "olmo-hybrid-sgd.py",
                    dict(attn_query_block=16, gdn_chunk=16)),
    "kimi_linear": ("kimi_small", "kimi-linear-sgd.py",
                    dict(kda_chunk=16, kda_block=4, mla_heads_at_once=2)),
    "mellum2": ("mellum2_small", "mellum2-sgd.py", dict(attn_query_block=16)),
    "ouro": ("ouro_small", "ouro-sgd.py", dict(attn_query_block=16)),
    "lfm2": ("lfm2_small", "lfm2-sgd.py", dict(attn_query_block=16)),
    "sdar": ("sdar_small", "sdar-sgd.py", dict(attn_query_block=16)),
}
COUNTS, BUDGETS = (9, 3, 1), (1.0, 3.0, 9.0)
#: what up to nine steps of a small lane make of the last two bits of its
#: initial weights, of a loss, in the median of a bracket's 13 (0 to 2.2e-4
#: measured over the six lanes; a lane that diverges reads up to 0.2, and
#: the small Ouro bracket's last promotion differs)
LAST_BITS = 1e-3


@pytest.fixture(scope="module", params=sorted(LANES))
def made(request):
    """``(the lane's module, its configuration, its eval_fn)``."""
    small, builder, sizes = LANES[request.param]
    small = importlib.import_module(small)
    sys.modules.setdefault("program", small.load("program.py"))
    cfg = small.load("configs", builder).lane_config(small.SMALL)._replace(**sizes)
    module = getattr(workloads, request.param)
    maker = getattr(module, "make_%s_eval_fn" % request.param)
    return module, cfg, maker(cfg, data_seed=small.SMALL["data_seed"])


def bracket_of(eval_fn):
    """A fresh function a call: ``jax.jit`` keeps a function's trace, and
    what the trace does follows ``fused._device_memory_bytes``."""
    def bracket(vectors):
        counters = []
        stages = fused.fused_sh_bracket(
            eval_fn, vectors, COUNTS, BUDGETS, lane_counters=counters)
        return stages, counters

    return bracket


def same_bits(a, b):
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y, equal_nan=True) for x, y in zip(a, b))


def test_init_is_the_scaling_of_the_shared_draw(made):
    """``init(s)`` is ``scale(shared(), s)`` leaf by leaf, the tied head, the
    norms and the biases with the rest: bit for bit in one program, and to
    the last two bits where the draw is an operand of its scaling (61 % of
    a leaf's entries differ, by 2 at most; what is not drawn not at all);
    what is shared is the drawn leaves alone, whatever the scale."""
    module, cfg, eval_fn = made
    name = module.__name__.rsplit(".", 1)[-1]
    init = lane.Init(getattr(module, "init_%s_params" % name), jax.random.key(1), cfg)
    scale = jnp.float32(0.37)
    whole = jax.jit(init)(scale)
    shared = jax.jit(init.shared)()
    assert same_bits(whole, jax.jit(lambda s: init.scale(init.shared(), s))(scale))
    apart = jax.jit(init.scale)(shared, scale)
    assert jax.tree.structure(whole) == jax.tree.structure(apart)
    for got, want in zip(jax.tree.leaves(apart), jax.tree.leaves(whole)):
        np.testing.assert_array_max_ulp(got, want, maxulp=3)
    assert jax.tree.structure(whole) == jax.tree.structure(init.scale(shared, 2.0))
    leaves = [n.rsplit("/", 1)[-1] for n in shared]
    assert "embed" in leaves and not [
        n for n in leaves if n.startswith("norm") or n.endswith(("_norm", "_bias"))]
    assert sum(x.size for x in shared.values()) < sum(
        x.size for x in jax.tree.leaves(whole))
    # the lane's maker states the same half
    stated = jax.eval_shape(eval_fn.lane_facts.shared)
    assert {n: x.shape for n, x in stated.items()} == {n: x.shape for n, x in shared.items()}


def makes_the_draw_itself(eval_fn):
    """The lane with nothing stated as shared: every evaluation makes the
    unit draw, the tree a bracket would hand it, as an operand of its
    scaling (behind a barrier: in one fusion with the scaling the compiler
    folds the draw's last factor into the scale)."""
    facts = eval_fn.lane_facts
    make = lambda: jax.lax.optimization_barrier(facts.shared())

    def with_counters(vec, budget):
        return facts.with_counters(vec, budget, make())

    def one(vec, budget):
        return eval_fn(vec, budget, make())

    one.lane_facts = facts._replace(shared=None, with_counters=with_counters)
    return one


def bracket_under(memory, eval_fn, vectors, monkeypatch):
    """Where the bracket's jaxpr draws its random bits, ``init_draws`` and
    the bracket's results, on a device of ``memory`` bytes."""
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: memory)
    assert fused.lanes_at_once(eval_fn, COUNTS[0]) == 1
    places = random_bits(jax.make_jaxpr(bracket_of(eval_fn))(vectors).jaxpr)
    return places, fused.init_draws(eval_fn, COUNTS), jax.jit(bracket_of(eval_fn))(vectors)


def test_a_bracket_handed_the_draw_reads_the_same_bits(made, monkeypatch):
    """13 evaluations in turn: with the draw made once before their loop (a
    device a byte short of two lanes: one lane and its draw) and with every
    evaluation making the same tree itself, the losses, the promotions and
    the counters are the same bits. On a device of one lane and a byte the
    bracket is the one of a lane that states nothing as shared, bit for bit:
    every evaluation draws and scales in one, inside the loop, and reads
    what the last bits of its initial weights leave of the held bracket's
    losses (a lane that diverges amplifies them; most do not)."""
    _, _, eval_fn = made
    facts = eval_fn.lane_facts
    vectors = jax.random.uniform(jax.random.key(3), (COUNTS[0], 4))
    (outside, inside), draws, held = bracket_under(
        2 * facts.bytes - 1, eval_fn, vectors, monkeypatch)
    assert outside > 0 and inside == 0 and draws == 1
    stages, counters = held
    assert [len(losses) for _, losses in stages] == list(COUNTS)
    assert np.isfinite(np.concatenate([losses for _, losses in stages])).any()
    assert len(counters) == (len(COUNTS) if facts.counters else 0)
    (outside, inside), draws, itself = bracket_under(
        2 * facts.bytes - 1, makes_the_draw_itself(eval_fn), vectors, monkeypatch)
    assert outside == 0 and inside > 0 and draws == sum(COUNTS)
    assert same_bits(held, itself)
    (outside, inside), draws, drawn = bracket_under(
        facts.bytes + 1, eval_fn, vectors, monkeypatch)
    assert outside == 0 and inside > 0 and draws == sum(COUNTS)
    nothing_shared = lambda vec, budget: eval_fn(vec, budget)
    nothing_shared.lane_facts = facts._replace(shared=None)
    assert same_bits(drawn, jax.jit(bracket_of(nothing_shared))(vectors))
    got, want = (np.concatenate([losses for _, losses in s]) for s in (stages, drawn[0]))
    both = np.isfinite(got) & np.isfinite(want)
    assert both.sum() > len(got) // 2
    assert np.median(np.abs(got - want)[both] / np.abs(want)[both]) < LAST_BITS


def test_change_draws_for_itself(made, monkeypatch):
    """``eval_fn.change`` takes no draw and is handed none: whatever the
    device holds it draws, once, before its loop over the steps, and what it
    returns has a leaf for every parameter."""
    module, cfg, eval_fn = made
    vec = jnp.asarray([0.6, 0.5, 0.4, 0.45], jnp.float32)
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: 2 * eval_fn.lane_facts.bytes)
    traced = jax.make_jaxpr(eval_fn.change, return_shape=True)(vec, 1.0)
    outside, inside = random_bits(traced[0].jaxpr)
    assert outside > 0 and inside == 0
    name = module.__name__.rsplit(".", 1)[-1]
    params = jax.eval_shape(
        lambda: getattr(module, "init_%s_params" % name)(jax.random.key(1), cfg, 1.0))
    assert jax.tree.structure(traced[1]) == jax.tree.structure(params)
    assert [x.shape for x in jax.tree.leaves(traced[1])] == [
        x.shape for x in jax.tree.leaves(params)]
    with pytest.raises(TypeError):
        eval_fn.change(vec, 1.0, {})


# -------------------------------------------------------- the loops' rule
def _toy(lane_bytes, table):
    """A lane whose evaluations share a table of ``table`` floats (4 bytes
    each); ``handed`` says, trace by trace, whether an evaluation got it."""
    handed = []

    def make():
        return {"table": jnp.arange(table, dtype=jnp.float32)}

    def with_counters(vec, budget, held=None):
        handed.append(held is not None)
        held = make() if held is None else held
        loss = (vec ** 2).sum() / budget + held["table"].sum()
        return loss, jnp.stack([vec[0], budget])

    def eval_fn(vec, budget, held=None):
        return with_counters(vec, budget, held)[0]

    eval_fn.lane_facts = fused.LaneFacts(
        bytes=lane_bytes, counters=("first", "budget"), with_counters=with_counters,
        traced_budget=True, shared=make)
    return eval_fn, handed


@pytest.mark.parametrize("memory, handed, draws", [
    # a backend that does not say, or all side by side: a rung is one trace
    # of the evaluation, which makes the table itself, once
    (None, [False] * 3, 3), (1000, [False] * 3, 3), (92, [False] * 3, 3),
    # five at once: the rung of nine is a map of two turns (traced for five
    # lanes, then for four), handed the table that fits beside five lanes
    (59, [True, True, False, False], 3),
    (57, [False] * 4, 2 + 1 + 1),   # ... or not, a byte short: once a turn
    (18, [True], 1),            # one at once: the bracket is one loop, held
    (17, [False], 13),          # ... which the table does not fit beside the lane
])
def test_the_draw_is_held_where_it_fits_beside_the_lanes(monkeypatch, memory, handed, draws):
    """Lanes of 10 bytes and a table of 8: a loop that takes the lanes in
    turn (a rung's ``map``, or the bracket's one loop) is handed the table
    where it fits the device beside the lanes of a turn, one test of size;
    a rung that is one turn (a ``vmap``, a single lane) is as it was."""
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: memory)
    eval_fn, was_handed = _toy(10, table=2)
    vectors = jax.random.uniform(jax.random.key(0), (9, 2))
    assert fused.init_draws(eval_fn, COUNTS) == draws
    counters = []
    stages = fused.fused_sh_bracket(eval_fn, vectors, COUNTS, BUDGETS, lane_counters=counters)
    assert was_handed == handed
    np.testing.assert_allclose(
        stages[0][1], (np.asarray(vectors) ** 2).sum(-1) + 1.0, rtol=1e-6)
    assert [c.shape for c in counters] == [(9, 2), (3, 2), (1, 2)]


@pytest.mark.parametrize("memory", [None, 10])
def test_a_lane_that_shares_nothing_is_evaluated_as_before(monkeypatch, memory):
    """Facts without ``shared``, and no facts at all: the evaluation is
    called with a vector and a budget and nothing else (a third argument
    would raise), side by side and in turn, and draws an evaluation."""
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: memory)
    vectors = jax.random.uniform(jax.random.key(0), (9, 2))

    def with_counters(vec, budget):
        return (vec ** 2).sum() / budget, jnp.stack([vec[0]])

    def stated(vec, budget):
        return with_counters(vec, budget)[0]

    stated.lane_facts = fused.LaneFacts(
        bytes=10, counters=("first",), with_counters=with_counters, traced_budget=True)
    assert fused.init_draws(stated, COUNTS) == (3 if memory is None else sum(COUNTS))
    for eval_fn in (stated, lambda vec, budget: (vec ** 2).sum() / budget):
        counters = []
        stages = fused.fused_sh_bracket(eval_fn, vectors, COUNTS, BUDGETS, lane_counters=counters)
        np.testing.assert_allclose(stages[0][1], (np.asarray(vectors) ** 2).sum(-1), rtol=1e-6)
        assert len(counters) == (3 if eval_fn is stated else 0)
        rung = fused.eval_lanes(eval_fn, vectors, 3.0)
        np.testing.assert_allclose(rung, (np.asarray(vectors) ** 2).sum(-1) / 3, rtol=1e-6)
