"""Builder of the ``branin-bohb`` configuration: the objective is a plain
function, so the evaluation object is the function itself."""

import program


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    return program.make_sweep(
        branin_space, {"eval_fn": branin_from_vector}, config, traffic, devices)
