"""Packaging (reference: HpBandSter ships on PyPI via setup.py, SURVEY.md §2)."""

from setuptools import find_packages, setup

setup(
    name="hpbandster_tpu",
    version="0.1.0",
    description=(
        "TPU-native distributed hyperparameter optimization: HyperBand/BOHB "
        "with batched, mesh-sharded successive halving in JAX"
    ),
    long_description=open("README.md").read(),
    long_description_content_type="text/markdown",
    packages=find_packages(include=["hpbandster_tpu", "hpbandster_tpu.*"]),
    python_requires=">=3.11",
    install_requires=[
        # written for the one installed stack (jax 0.9.0 / libtpu 0.0.34):
        # jax.shard_map(check_vma=), jax.core.Tracer, pltpu.CompilerParams
        "jax>=0.9.0",
        "numpy",
    ],
    extras_require={
        "viz": ["matplotlib"],
        "analysis": ["pandas"],
        "test": ["pytest"],
    },
    license="BSD-3-Clause",
)
