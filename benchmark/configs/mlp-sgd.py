"""Builder of the ``mlp-sgd`` configuration: one ensemble of vmapped SGD
lanes, its data set and initial-weight key made on the device from the
configuration's data seed, once."""

import program


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.ensemble import make_mlp_ensemble
    from hpbandster_tpu.workloads.mlp import MLPConfig, mlp_space

    ensemble = make_mlp_ensemble(
        MLPConfig(**config["mlp"]), data_seed=config["data_seed"])
    return program.make_sweep(
        mlp_space, {"stateful_eval": ensemble}, config, traffic, devices)
