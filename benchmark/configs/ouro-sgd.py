"""Builder of the ``ouro-sgd`` configuration: one chip's share of Ouro-2.6B
(layers run ``total_ut_steps`` times with one set of weights, an exit after
every pass) as a stateless ``eval_fn``, its tokens and its initial-weight
key made from the configuration's data seed, once."""

import program


def lane_config(config):
    """The program's ``OuroConfig`` from the configuration's file: the
    published widths under their published keys, the cut as
    ``num_hidden_layers`` and the data under ``train``."""
    from hpbandster_tpu.workloads.ouro import OuroConfig

    if set(config["layer_types"]) != {"full_attention"} or config["use_sliding_window"]:
        raise ValueError("ouro-sgd: every layer is full attention, no window")
    if config["rope_scaling"] is not None or config["tie_word_embeddings"]:
        raise ValueError("ouro-sgd: plain RoPE, an untied head")
    if config["early_exit_threshold"] != 1:
        raise ValueError("ouro-sgd: a lane runs every pass and reports the last "
                         "exit (early_exit_threshold 1)")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("ouro-sgd: one layer_types entry a layer held")
    return OuroConfig(
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        total_ut_steps=config["total_ut_steps"],
        exit_entropy_beta=config["exit_entropy_beta"],
        num_layers=config["num_hidden_layers"],
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.ouro import make_ouro_eval_fn, ouro_space

    eval_fn = make_ouro_eval_fn(lane_config(config), data_seed=config["data_seed"])
    ahead = [_ahead(_compile_the_reference, config), _ahead(_compile_the_change, eval_fn)]
    one_sweep = program.make_sweep(
        ouro_space, {"eval_fn": eval_fn}, config, traffic, devices)

    def lane_change(hparams, steps):
        return _lane_change(ahead[1]() or _compile_the_change(eval_fn), hparams, steps)

    def sweep(seed):
        raw = one_sweep(seed)
        for compiled in ahead:   # a wait in the first warm-up sweep alone
            compiled()
        extract = raw["extract"]
        raw["extract"] = lambda: dict(extract(), lane_change=lane_change)
        return raw

    return sweep


def _lane_change(change, hparams, steps):
    """The program's trainer (``change``: :func:`_compile_the_change`) on the
    lane of ``hparams = (lr, momentum, weight_decay, init_scale)``: what
    ``steps`` steps changed, under the reference's names (``layers`` of
    ``l<i>``). The lane's vector is the unit cube's, as a sweep hands it."""
    import numpy as np

    lr, momentum, wd, init_scale = (float(x) for x in hparams)
    vec = np.asarray([(np.log10(lr) + 4.0) / 4.0, momentum / 0.99,
                      (np.log10(wd) + 7.0) / 5.0, (np.log10(init_scale) + 1.0) / 2.0],
                     np.float32)
    return change(vec, np.float32(steps))


def _ahead(compile_it, *args):
    """``compile_it(*args)`` on a thread of its own, beside the program's
    own compilation (a minute or more on a few of the host's cores), into
    the same compile cache: ``-> wait() -> what it returned``, None where it
    raised (whoever needs it compiles for itself then, and sees why)."""
    import threading

    done = [None]

    def work():
        try:
            done[0] = compile_it(*args)
        except Exception:  # noqa: BLE001 - see the docstring
            pass

    thread = threading.Thread(target=work, daemon=True)
    thread.start()

    def wait():
        thread.join()
        return done[0]

    return wait


def _compile_the_reference(config):
    """The plain reference's functions (``kimi-linear-sgd.py``'s way): it
    takes nothing from the program and gives it nothing."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference", "ouro-sgd.py")
    spec = importlib.util.spec_from_file_location("bench_reference_ahead", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.compile_ahead(config)


def _compile_the_change(eval_fn):
    """``(vec f32[4], steps f32[]) -> what the steps changed``, the layers'
    stacked leaves taken apart into ``l<i>``; compiled at the compiler's
    quickest effort: it runs twice a comparison."""
    import jax
    import jax.numpy as jnp

    def change(vec, steps):
        tree = dict(eval_fn.change(vec, steps))
        stacked = tree.pop("layers")
        n_layers = jax.tree.leaves(stacked)[0].shape[0]
        tree["layers"] = {"l%d" % i: jax.tree.map(lambda x: x[i], stacked)
                          for i in range(n_layers)}
        return tree

    return jax.jit(change, compiler_options={"exec_time_optimization_effort": -1.0}).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32), jax.ShapeDtypeStruct((), jnp.float32)).compile()
