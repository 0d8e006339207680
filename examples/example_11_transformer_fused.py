"""Example 11: HPO over a transformer — attention on the MXU, fully fused.

A decoder-only transformer trains on the synthetic COPY task (the second
half of each sequence is predictable only by attending back across the
separator — workloads/transformer.py), and FusedBOHB compiles the whole
multi-bracket sweep into one device program: KDE proposals, every config's
full training run, and top-k promotions all execute on the accelerator.

The attention/MLP matmuls run in bfloat16 with float32 accumulation — the
MXU's native regime — so on real TPU hardware this rung reports meaningful
MFU.

Reference analog: the reference's model-family examples are the MNIST
MLP/Keras/PyTorch workers (SURVEY.md §2 "examples"); this rung extends the
same eval_fn contract to the attention family.
"""

import argparse
import time

import jax

from hpbandster_tpu.optimizers import FusedBOHB
from hpbandster_tpu.parallel import config_mesh
from hpbandster_tpu.workloads.transformer import (
    TRANSFORMER_TARGET_VAL_ACCURACY,
    TransformerConfig,
    make_transformer_error_fn,
    transformer_space,
)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n_iterations", type=int, default=2)
    p.add_argument("--min_budget", type=float, default=9)
    p.add_argument("--max_budget", type=float, default=81)
    p.add_argument("--tiny", action="store_true",
                   help="CPU-sized model/data (the test-suite config)")
    args = p.parse_args()

    cfg = (
        TransformerConfig(vocab=16, prefix_len=7, d_model=32, n_heads=2,
                          n_layers=2, d_ff=128, n_train=128, n_val=64,
                          batch_size=64)
        if args.tiny else TransformerConfig()
    )
    cs = transformer_space(seed=0)
    devices = jax.devices()
    mesh = config_mesh(devices) if len(devices) > 1 else None

    opt = FusedBOHB(
        configspace=cs,
        eval_fn=make_transformer_error_fn(cfg),
        run_id="example11",
        min_budget=args.min_budget,
        max_budget=args.max_budget,
        eta=3,
        seed=0,
        mesh=mesh,
        min_points_in_model=5,
    )
    t0 = time.perf_counter()
    res = opt.run(n_iterations=args.n_iterations)
    dt = time.perf_counter() - t0
    opt.shutdown()

    traj = res.get_incumbent_trajectory()
    acc = 1.0 - traj["losses"][-1]
    print(f"devices: {len(devices)} ({devices[0].platform})")
    print(f"evaluated {opt.total_evaluated} configs in {dt:.2f}s "
          f"({opt.total_evaluated / dt:.1f} configs/s)")
    print(f"incumbent copied-half val accuracy: {acc:.3f} "
          f"(chance {1.0 / (cfg.vocab):.3f}, documented target "
          f"{TRANSFORMER_TARGET_VAL_ACCURACY} on the default config)")


if __name__ == "__main__":
    main()
