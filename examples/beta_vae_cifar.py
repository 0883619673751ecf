"""β-VAE on CIFAR-10, N concurrent trials sweeping β (BASELINE.md
config 3: "8 trials x 4-chip submesh, stress per-trial all-reduce").

Same subgroup scaffolding as vae_hpo.py — only the model (ConvVAE) and
the swept hyperparameter (β instead of epochs) change, via the driver's
``model_builder`` hook.

Run (8 virtual CPU devices, 8 trials of 1 device each):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/beta_vae_cifar.py --ngroups 8 --epochs 1
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import multidisttorch_tpu as mdt  # noqa: E402
from multidisttorch_tpu.data import load_cifar10  # noqa: E402
from multidisttorch_tpu.hpo import TrialConfig, all_completed, run_hpo  # noqa: E402
from multidisttorch_tpu.models import ConvVAE  # noqa: E402


def main():
    parser = argparse.ArgumentParser(description="beta-VAE CIFAR-10 HPO (TPU-native)")
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--ngroups", type=int, default=8)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--latent-dim", type=int, default=64)
    parser.add_argument("--base-channels", type=int, default=32)
    parser.add_argument("--out-dir", default="results-beta-vae")
    parser.add_argument("--synthetic-size", type=int, default=None)
    args = parser.parse_args()

    mdt.initialize_runtime()
    train_data = load_cifar10(train=True, synthetic_size=args.synthetic_size)
    test_data = load_cifar10(
        train=False,
        synthetic_size=args.synthetic_size and max(args.batch_size, args.synthetic_size // 6),
    )

    # β sweep: one trial per subgroup, β doubling per trial.
    configs = [
        TrialConfig(
            trial_id=g,
            epochs=args.epochs,
            batch_size=args.batch_size,
            lr=args.lr,
            beta=float(2**g) / 2.0,  # 0.5, 1, 2, 4, ...
            seed=g,
        )
        for g in range(args.ngroups)
    ]

    results = run_hpo(
        configs,
        train_data,
        test_data,
        out_dir=args.out_dir,
        model_builder=lambda cfg: ConvVAE(
            latent_dim=args.latent_dim, base_channels=args.base_channels
        ),
    )
    for r in results:
        print(
            f"trial {r.trial_id} (beta={r.config.beta}) [{r.status}]: "
            f"test loss {r.final_test_loss:.2f}, wall {r.wall_s:.2f}s"
        )
    # A diverged (or, under resilient=True, failed) trial is a recorded
    # result, not an exception: the exit code says whether all trained.
    return 0 if all_completed(results) else 1


if __name__ == "__main__":
    sys.exit(main())
