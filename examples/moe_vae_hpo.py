"""Mixture-of-experts VAE HPO over device subgroups, with expert
parallelism INSIDE each trial.

Same scaffolding as ``examples/vae_hpo.py`` (the reference's trial
dispatch, ``/root/reference/vae-hpo.py:177-202``), composed two ways:
the flagship model swaps to :class:`models.moe_vae.MoEVAE` via
``model_builder``, and ``--model-parallel m`` carves each trial's
submesh 2-D so ``param_shardings_builder`` shards the experts over the
trial's model axis — trial-parallel x data-parallel x expert-parallel
from one driver call. Each trial sweeps the expert count.

Run (8 virtual CPU devices; 2 trials x (2 data x 2 model) devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/moe_vae_hpo.py --ngroups 2 --epochs 1 \
            --model-parallel 2
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import multidisttorch_tpu as mdt  # noqa: E402
from multidisttorch_tpu.data import load_mnist  # noqa: E402
from multidisttorch_tpu.hpo import TrialConfig, all_completed, run_hpo  # noqa: E402
from multidisttorch_tpu.models import MoEVAE, moe_vae_ep_shardings  # noqa: E402


def main():
    parser = argparse.ArgumentParser(description="MoE-VAE HPO (TPU-native)")
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--ngroups", type=int, default=2)
    parser.add_argument(
        "--experts-base", type=int, default=2,
        help="trial g uses experts-base * 2^g experts",
    )
    parser.add_argument(
        "--model-parallel", type=int, default=1,
        help="model-axis extent per trial submesh; >1 shards each "
        "trial's experts over it (expert parallelism)",
    )
    parser.add_argument("--synthetic-size", type=int, default=2048)
    parser.add_argument("--out-dir", default="results-moe")
    args = parser.parse_args()

    mdt.initialize_runtime()
    train_data = load_mnist(train=True, synthetic_size=args.synthetic_size)
    test_data = load_mnist(
        train=False, synthetic_size=max(args.batch_size, args.synthetic_size // 6)
    )

    experts = {g: args.experts_base * (2**g) for g in range(args.ngroups)}
    configs = [
        TrialConfig(
            trial_id=g, epochs=args.epochs, batch_size=args.batch_size,
            seed=g, fused_steps=4,
        )
        for g in range(args.ngroups)
    ]

    results = run_hpo(
        configs,
        train_data,
        test_data,
        out_dir=args.out_dir,
        save_images=False,
        model_builder=lambda cfg: MoEVAE(
            hidden_dim=cfg.hidden_dim,
            latent_dim=cfg.latent_dim,
            num_experts=experts[cfg.trial_id],
        ),
        model_parallel=args.model_parallel,
        param_shardings_builder=(
            moe_vae_ep_shardings if args.model_parallel > 1 else None
        ),
    )
    for r in results:
        print(
            f"trial {r.trial_id} ({experts[r.trial_id]} experts) [{r.status}]: "
            f"train loss {r.final_train_loss:.4f}, "
            f"test loss {r.final_test_loss:.4f}, wall {r.wall_s:.2f}s"
        )
    # A diverged (or, under resilient=True, failed) trial is a recorded
    # result, not an exception: the exit code says whether all trained.
    return 0 if all_completed(results) else 1


if __name__ == "__main__":
    sys.exit(main())
