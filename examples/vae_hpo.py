"""Concurrent VAE HPO trials, one per device subgroup — TPU-native mirror
of /root/reference/vae-hpo.py (same CLI flags).

The reference: N process subgroups, each running a DDP-wrapped VAE on
MNIST, the trial's hyperparameter being ``epochs + group_id``
(vae-hpo.py:202). Here: N disjoint submeshes, each running a
jit-compiled data-parallel train step, dispatched concurrently by the
host driver with no cross-trial barriers. Extra flags expose the knobs
the reference hard-codes (lr, β, data sharding mode).

Run (8 virtual CPU devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/vae_hpo.py --epochs 1 --ngroups 2
"""

import argparse
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import multidisttorch_tpu as mdt  # noqa: E402
from multidisttorch_tpu.data import load_mnist  # noqa: E402
from multidisttorch_tpu.hpo import TrialConfig, all_completed, run_hpo  # noqa: E402


def main():
    parser = argparse.ArgumentParser(description="VAE MNIST Example (TPU-native)")
    # Reference flags, same names and defaults (vae-hpo.py:178-194):
    parser.add_argument(
        "--batch-size", type=int, default=128, metavar="N",
        help="input batch size for training (default: 128)",
    )
    parser.add_argument(
        "--epochs", type=int, default=3, metavar="N",
        help="number of epochs to train (default: 3)",
    )
    parser.add_argument("--ngroups", type=int, default=2, help="number of groups")
    # Knobs the reference hard-codes:
    parser.add_argument("--lr", type=float, default=1e-3, help="Adam lr (vae-hpo.py:131)")
    parser.add_argument("--beta", type=float, default=1.0, help="beta-VAE KL weight")
    parser.add_argument("--out-dir", default="results", help="output root (per-trial subdirs)")
    parser.add_argument(
        "--shard-across-trials", action="store_true",
        help="reproduce the reference's cross-trial data sharding (SURVEY.md Q1)",
    )
    parser.add_argument(
        "--synthetic-size", type=int, default=None,
        help="rows for the synthetic fallback dataset (default: MNIST-sized)",
    )
    parser.add_argument(
        "--fused-steps", type=int, default=10,
        help="train steps fused into one device dispatch via lax.scan "
        "(default 10 = the log cadence; 1 reproduces the reference's "
        "one-dispatch-per-batch loop shape)",
    )
    parser.add_argument(
        "--eval-sampled", action="store_true",
        help="reproduce the reference's sampled-z test loss "
        "(vae-hpo.py:101-105) instead of the default posterior-mean eval",
    )
    parser.add_argument(
        "--remat", action="store_true",
        help="rematerialize activations in the backward pass "
        "(jax.checkpoint) — trade FLOPs for HBM",
    )
    args = parser.parse_args()

    nproc, pid = mdt.initialize_runtime()
    ndev, _ = mdt.device_world()
    print(f"devices: {ndev}, processes: {nproc}")

    train_data = load_mnist(train=True, synthetic_size=args.synthetic_size)
    test_data = load_mnist(
        train=False,
        synthetic_size=args.synthetic_size and max(args.batch_size, args.synthetic_size // 6),
    )

    # The reference's HPO sweep: trial g trains epochs + g epochs
    # (vae-hpo.py:202). Config generalizes the rest of the knobs.
    configs = [
        TrialConfig(
            trial_id=g,
            epochs=args.epochs + g,
            batch_size=args.batch_size,
            lr=args.lr,
            beta=args.beta,
            seed=g,
            fused_steps=args.fused_steps,
            eval_sampled=args.eval_sampled,
            remat=args.remat,
        )
        for g in range(args.ngroups)
    ]

    results = run_hpo(
        configs,
        train_data,
        test_data,
        out_dir=args.out_dir,
        shard_across_trials=args.shard_across_trials,
    )
    for r in results:
        print(
            f"trial {r.trial_id} [{r.status}]: {r.steps} steps, "
            f"final train loss {r.final_train_loss:.4f}, "
            f"test loss {r.final_test_loss:.4f}, wall {r.wall_s:.2f}s "
            f"-> {r.out_dir}"
        )
    # A diverged (or, under resilient=True, failed) trial is a recorded
    # result, not an exception: the exit code says whether all trained.
    return 0 if all_completed(results) else 1


if __name__ == "__main__":
    sys.exit(main())
