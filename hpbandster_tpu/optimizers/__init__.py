"""Optimizers: Master subclasses wiring iterations + config generators."""

from hpbandster_tpu.optimizers.hyperband import HyperBand  # noqa: F401
from hpbandster_tpu.optimizers.bohb import BOHB  # noqa: F401
from hpbandster_tpu.optimizers.randomsearch import RandomSearch  # noqa: F401
from hpbandster_tpu.optimizers.h2bo import H2BO  # noqa: F401
from hpbandster_tpu.optimizers.fused_bohb import (  # noqa: F401
    FusedBOHB,
    FusedH2BO,
    FusedHyperBand,
    FusedRandomSearch,
    sweep_instruction_facts,
    sweep_phase_maps,
)
