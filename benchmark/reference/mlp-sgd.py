"""Plain reference of ``mlp-sgd``: the MLP, its data and momentum SGD
written from the equations ``workloads/mlp.py`` and ``ensemble.py`` state,
in straightforward ``jax.numpy``. Imports nothing of the program and takes
nothing it made: data and initial weights come from the seed again.

A seeded sample of lanes that started at a budget of 9 steps or fewer is
retrained from its reported hyperparameters, and the validation loss after
1, 3 and 9 cumulative steps is held against what the window reported there,
before hundreds of steps have amplified rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_LANES = 64
SAMPLE_INCUMBENTS = 8
MARKS = (1, 3, 9)
#: per loss: gap = |reported - reference| / (1 + |reference|). Readings the
#: limits were set from (PERF.md section 2, chip runs of PR 24). Median gap:
#: sound runs at most 5.8e-6 over 58 runs (same bfloat16-input matmuls,
#: another order of accumulation), the bfloat16 control at least 3.1e-4 over
#: 12 seeds. The 90th percentile is there to catch a lost state, which shows
#: only in the losses of continued training of lanes whose learning rate is
#: not small: sound runs read at most 3.7e-4 (the few lanes whose large
#: learning rate amplifies rounding), a step that returns its state unchanged
#: at least 9.5e-3.
LOSS_GAP_MEDIAN_LIMIT = 5e-5
LOSS_GAP_P90_LIMIT = 2e-3
#: no cell of BENCHMARK.json uses this yet (PERF.md section 7): on the chip, 729
#: steps drive the incumbent's loss to exactly 0 in float32 and in bfloat16, so
#: no chip reading separates them. Set from the CPU test at 9 steps: sound
#: 2.8e-7, bfloat16 control 4.2e-3. A PR that enters such a cell sets it anew.
INCUMBENT_GAP_LIMIT = 1e-4


def decode(vector):
    """Unit-cube vector -> (lr, momentum, weight_decay, init_scale), the
    codec ``workloads/mlp.py`` documents: log ranges for all but momentum."""
    v = np.asarray(vector, np.float64)
    return [10.0 ** (-4.0 + 4.0 * v[0]), 0.99 * v[1],
            10.0 ** (-7.0 + 5.0 * v[2]), 10.0 ** (-1.0 + 2.0 * v[3])]


def dataset(mlp, data_seed, dtype):
    """Gaussian class blobs: centres 2 N(0, 1), points centre + 1.5 N(0, 1)."""
    kc, kx, kv = jax.random.split(jax.random.key(data_seed), 3)
    centers = 2.0 * jax.random.normal(kc, (mlp["n_classes"], mlp["d_in"]))

    def draw(k, n):
        k1, k2 = jax.random.split(k)
        labels = jax.random.randint(k1, (n,), 0, mlp["n_classes"])
        x = centers[labels] + 1.5 * jax.random.normal(k2, (n, mlp["d_in"]))
        return x.astype(dtype), labels

    return draw(kx, mlp["n_train"]), draw(kv, mlp["n_val"])


def init_params(mlp, key, init_scale, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    d, w, c = mlp["d_in"], mlp["width"], mlp["n_classes"]
    s1, s2 = init_scale * (2.0 / d) ** 0.5, init_scale * (2.0 / w) ** 0.5
    p = {
        "w1": s1 * jax.random.normal(k1, (d, w)), "b1": jnp.zeros((w,)),
        "w2": s2 * jax.random.normal(k2, (w, w)), "b2": jnp.zeros((w,)),
        "w3": s2 * jax.random.normal(k3, (w, c)), "b3": jnp.zeros((c,)),
    }
    return {k: v.astype(dtype) for k, v in p.items()}


def forward(p, x):
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    h = jnp.tanh(h @ p["w2"] + p["b2"])
    return h @ p["w3"] + p["b3"]


def xent(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def reference_losses(mlp, data_seed, hparams, marks=MARKS, dtype=jnp.float32):
    """``f[n, len(marks)]``: lane ``i``'s validation loss after each mark of
    cumulative steps, trained from ``hparams[i] = (lr, momentum,
    weight_decay, init_scale)``. v <- m v + g + wd p; p <- p - lr v;
    minibatch ``t`` is rows ``(t mod n_batches) * batch`` onward."""
    (x_tr, y_tr), (x_val, y_val) = dataset(mlp, data_seed, dtype)
    key = jax.random.key(data_seed + 1)
    batch = min(mlp["batch_size"], mlp["n_train"])
    n_batches = max(mlp["n_train"] // batch, 1)
    grad = jax.grad(lambda p, xb, yb: xent(forward(p, xb), yb))

    def lane(hp):
        lr, momentum, wd, init_scale = (h.astype(dtype) for h in hp)
        p = init_params(mlp, key, init_scale, dtype)

        def step(carry, t):
            p, v = carry
            start = (t % n_batches) * batch
            g = grad(p, jax.lax.dynamic_slice_in_dim(x_tr, start, batch),
                     jax.lax.dynamic_slice_in_dim(y_tr, start, batch))
            v = jax.tree.map(lambda vi, gi, pi: momentum * vi + gi + wd * pi, v, g, p)
            p = jax.tree.map(lambda pi, vi: pi - lr * vi, p, v)
            return (p, v), None

        carry, done, out = (p, jax.tree.map(jnp.zeros_like, p)), 0, []
        for mark in marks:
            carry, _ = jax.lax.scan(step, carry, jnp.arange(done, mark))
            done = mark
            out.append(xent(forward(carry[0], x_val), y_val))
        return jnp.stack(out)

    return np.asarray(
        jax.jit(jax.vmap(lane))(jnp.asarray(hparams, jnp.float32)), np.float64)


def sample_rows(records, seed):
    """For a seeded sample of lanes: their reported hyperparameters
    ``f[n, 4]`` and, per lane, ``{mark index: reported loss}`` (NaN where
    the program masked a crash). Every other lane is drawn from those that
    reached the last mark, so that losses of continued training, which a
    lost state would spoil, are about half of what is compared."""
    rng = np.random.default_rng(seed)
    hparams, reported = [], []
    for n in range(SAMPLE_LANES):
        rec = records[rng.integers(len(records))]
        top = MARKS[-1] if n % 2 else MARKS[0]
        rows = np.flatnonzero((rec["budget"] >= top - 0.5)
                              & (rec["budget"] <= MARKS[-1] + 0.5))
        pick = rows[rng.integers(len(rows))]
        same = (rec["bracket"] == rec["bracket"][pick]) & (rec["lane"] == rec["lane"][pick])
        losses = {}
        for j, mark in enumerate(MARKS):
            at = np.flatnonzero(same & np.isclose(rec["budget"], mark))
            if len(at):
                losses[j] = rec["loss"][at[0]]
        hparams.append([rec["config"][n][pick]
                        for n in ("lr", "momentum", "weight_decay", "init_scale")])
        reported.append(losses)
    return np.asarray(hparams), reported


def gap(got, ref):
    """|got - ref| / (1 + |ref|); a crash or an overflow is sound only
    where the reference has the same."""
    if np.isfinite(got) and np.isfinite(ref):
        return abs(got - ref) / (1 + abs(ref))
    same = (np.isnan(got) and np.isnan(ref)) or got == ref
    return 0.0 if same else np.inf


def compare(config, traffic, records, seed, control=False):
    """``[(name, value, limit)]``. With ``control`` the reference computed
    in bfloat16 stands in the program's place."""
    if records[0]["kind"] == "incumbent":
        return compare_incumbents(config, records, seed, control)
    hparams, reported = sample_rows(records, seed)
    data_seed = config["data_seed"]
    want = reference_losses(config["mlp"], data_seed, hparams)
    if control:
        low = reference_losses(config["mlp"], data_seed, hparams, dtype=jnp.bfloat16)
        reported = [{j: low[i, j] for j in losses}
                    for i, losses in enumerate(reported)]
    gaps = np.asarray([gap(got, want[i, j])
                       for i, losses in enumerate(reported)
                       for j, got in losses.items()])
    print("mlp-sgd %s: %d losses compared; gap quantiles 50/75/90/95/100 %%: %s" % (
        "control" if control else "reference", len(gaps),
        " ".join("%.3g" % q for q in np.quantile(gaps, [0.5, 0.75, 0.9, 0.95, 1.0]))))
    return [
        ("loss_gap_median", float(np.median(gaps)), LOSS_GAP_MEDIAN_LIMIT),
        ("loss_gap_p90", float(np.quantile(gaps, 0.9)), LOSS_GAP_P90_LIMIT),
    ]


def compare_incumbents(config, records, seed, control):
    """A sweep that returns only its incumbent: a seeded sample of the
    window's incumbents, each retrained from its vector to the budget it was
    reported at, has to reproduce its loss."""
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(records), min(SAMPLE_INCUMBENTS, len(records)), replace=False)
    hparams = [decode(records[i]["incumbent"]["vector"]) for i in picks]
    marks = (int(round(records[0]["budget"])),)
    want = reference_losses(config["mlp"], config["data_seed"], hparams, marks)
    got = [records[i]["incumbent"]["loss"] for i in picks]
    if control:
        got = reference_losses(config["mlp"], config["data_seed"], hparams, marks,
                               dtype=jnp.bfloat16)[:, 0]
    gaps = [gap(g, w) for g, w in zip(got, want[:, 0])]
    print("mlp-sgd %s: %d incumbents retrained to %d steps; gaps: %s" % (
        "control" if control else "reference", len(gaps), marks[0],
        " ".join("%.3g" % g for g in gaps)))
    widest = max(gaps)
    return [("incumbent_loss_gap_widest", float(widest), INCUMBENT_GAP_LIMIT)]
