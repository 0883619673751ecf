"""Concurrent LM trials, each sequence-parallel on its own submesh.

The composition the long-context mandate meets the reference's raison
d'être (concurrent per-subgroup trials, vae-hpo.py:122-174) in: carve
the job into N submeshes, and inside EACH one train a causal
TransformerLM with its context sharded T/k over that submesh's ring
(ring or ring-flash attention). Trials sweep the learning rate and run
under the same cooperative no-barrier dispatch as every other sweep.
``--model-parallel m`` adds a third axis: each trial's submesh becomes
(data x model), heads + q/k/v/proj + the MLP pair shard over the model
axis (2-D sequence x head attention) — trial x sequence x tensor
parallelism in one sweep. ``--moe E`` swaps in the MoE transformer
(E experts per block); with ``--model-parallel`` the experts claim the
model axis instead (trial x sequence x EXPERT parallelism).
``--latent-moe E`` swaps in the latent-attention LM instead
(``models/latent_moe.py``: rotary low-rank attention with q and k wider
than v, a dense layer and then dropless top-2-of-E sigmoid-routed
experts with a shared expert), at a toy size, its context on the ring.

Run (8 virtual CPU devices — two 4-device rings):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/lm_hpo.py --ngroups 2 --seq-len 128 --steps 40
    # two (2-ring x 2-TP) trials:
    ... python examples/lm_hpo.py --ngroups 2 --seq-len 64 --model-parallel 2
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

import multidisttorch_tpu as mdt  # noqa: E402
from multidisttorch_tpu.models.transformer import TransformerLM  # noqa: E402
from multidisttorch_tpu.ops.ring_attention import make_ring_attention  # noqa: E402
from multidisttorch_tpu.parallel.mesh import DATA_AXIS  # noqa: E402
from multidisttorch_tpu.train.lm import (  # noqa: E402
    create_lm_state,
    lm_chunk_sharding,
    make_lm_eval_step,
    make_lm_multi_step,
    make_lm_train_step,
)
from multidisttorch_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from multidisttorch_tpu.utils.profiling import (  # noqa: E402
    admission_line,
    admission_split,
)


def _plan_mpmd_pipeline(args) -> None:
    """Plan (and print) a 2-stage MPMD pipelined LM trial over this
    device world: the balanced param split, the slice-vector placement
    (``SlicePool.alloc_multi`` — the service's all-or-nothing rule),
    the GPipe schedule model, and the ZeRO sharded-update
    optimizer-memory table (docs/PARALLEL.md). Exits before training —
    the executing MPMD runner covers the VAE family; the LM family
    plugs into the same generic stage contract when a deep split
    lands."""
    from multidisttorch_tpu.parallel.pipeline import (
        analytic_bubble_fraction,
    )
    from multidisttorch_tpu.service.scheduler import SlicePool

    world = len(jax.devices())
    groups = mdt.setup_groups(1)
    model = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model,
        num_layers=args.layers, max_len=args.seq_len,
        attention=make_ring_attention(groups[0], causal=True),
    )
    abstract = jax.eval_shape(
        lambda rng: model.init(
            {"params": rng}, jnp.zeros((1, args.seq_len), jnp.int32)
        )["params"],
        jax.random.key(0),
    )
    leaves = jax.tree.leaves_with_path(abstract) if hasattr(
        jax.tree, "leaves_with_path"
    ) else [
        ((), leaf) for leaf in jax.tree.leaves(abstract)
    ]
    sizes = [int(np.prod(l.shape)) for _, l in leaves]
    total = sum(sizes)
    # Balanced 2-stage split by cumulative parameter count.
    acc, cut = 0, len(sizes)
    for i, s in enumerate(sizes):
        acc += s
        if acc >= total / 2:
            cut = i + 1
            break
    stage_params = [sum(sizes[:cut]), sum(sizes[cut:])]

    per_stage = max(1, world // 4)
    pool = SlicePool(world)
    starts = pool.alloc_multi([per_stage, per_stage])
    m = max(2, args.fused_steps)
    n_data = per_stage  # 1 device per slice in the example world
    opt_total = 2 * total * 4  # Adam mu+nu, f32
    opt_zero = opt_total // max(1, n_data)

    print(f"MPMD pipeline plan ({world}-device world, docs/PARALLEL.md)")
    print(
        f"  model: TransformerLM vocab={args.vocab} d_model="
        f"{args.d_model} layers={args.layers} -> {total:,} params"
    )
    print(
        f"  2-stage balanced split: stage0 {stage_params[0]:,} / "
        f"stage1 {stage_params[1]:,} params (cut after leaf {cut})"
    )
    print(
        f"  slice vector: sizes ({per_stage}, {per_stage}) -> "
        f"all-or-nothing starts {starts} "
        f"(SlicePool.alloc_multi, largest-first, rollback-on-failure)"
    )
    for mm in sorted({m, 4, 8, 16}):
        print(
            f"  schedule model: S=2 M={mm} -> bubble "
            f"{analytic_bubble_fraction(2, mm):.3f}  "
            "((S-1)/(S-1+M))"
        )
    print(
        f"  optimizer memory: replicated {opt_total:,} B/device -> "
        f"zero_update {opt_zero:,} B/device over data extent {n_data} "
        "(+ small replicated leaves)"
    )
    print(
        "  dry run: plan only — submit a pipeline_stages=2 VAE-family "
        "config to the sweep service (tests/test_pipeline_mpmd.py runs "
        "one) for an executing trial"
    )


def main():
    parser = argparse.ArgumentParser(
        description="trial-parallel x sequence-parallel LM sweep"
    )
    parser.add_argument("--ngroups", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--batch-size", type=int, default=2)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument(
        "--fused-steps", type=int, default=1, metavar="K",
        help="optimizer steps per device dispatch (make_lm_multi_step's "
        "lax.scan). 1 = a dispatch per step; larger K amortizes the "
        "host enqueue that otherwise caps concurrent trials "
        "(docs/DISPATCH.md sizing rule). Must divide --steps.",
    )
    parser.add_argument(
        "--ring-flash", action="store_true",
        help="flash-kernel hops (ops/pallas_attention.py) inside each "
        "trial's K/V ring",
    )
    parser.add_argument(
        "--model-parallel", type=int, default=1,
        help="model-axis extent per trial: heads + q/k/v/proj + MLP "
        "pair shard over it (2-D sequence x head attention), composing "
        "trial x sequence x tensor parallelism in one sweep",
    )
    parser.add_argument(
        "--moe", type=int, default=0, metavar="E",
        help="use the MoE transformer with E experts per block; with "
        "--model-parallel the experts shard over the model axis "
        "(expert parallelism) while the context rides the ring",
    )
    parser.add_argument(
        "--latent-moe", type=int, default=0, metavar="E",
        help="use the latent-attention LM (models/latent_moe.py) with a "
        "leading dense layer, then E dropless sigmoid-routed experts a "
        "block, 2 a token, and a shared expert; prints the assignments "
        "each expert received in the last step",
    )
    parser.add_argument(
        "--pipeline", action="store_true",
        help="plan a cross-submesh MPMD pipelined LM trial "
        "(docs/PARALLEL.md): balanced 2-stage param split, the "
        "all-or-nothing slice-vector placement over this world, the "
        "GPipe schedule model, and the ZeRO optimizer-memory table — "
        "then exit (the executing MPMD runner covers the VAE family; "
        "see tests/test_pipeline_mpmd.py)",
    )
    parser.add_argument(
        "--dry-run", action="store_true",
        help="with --pipeline: plan only (implied; kept explicit for "
        "the CI smoke)",
    )
    args = parser.parse_args()
    if args.dry_run and not args.pipeline:
        parser.error("--dry-run only applies with --pipeline")
    if args.fused_steps < 1 or args.steps % args.fused_steps:
        parser.error(
            f"--fused-steps {args.fused_steps} must be >= 1 and divide "
            f"--steps {args.steps}"
        )

    mdt.initialize_runtime()
    if args.pipeline:
        _plan_mpmd_pipeline(args)
        return
    if args.latent_moe and (args.moe or args.model_parallel > 1 or args.ring_flash):
        parser.error(
            "--latent-moe runs by itself: its experts are one chip's "
            "(no expert exchange yet) and ring-flash hops take one head width"
        )
    if args.model_parallel > 1:
        if args.moe:
            if args.moe % args.model_parallel:
                parser.error(
                    f"--model-parallel {args.model_parallel} must "
                    f"divide the --moe {args.moe} experts (whole "
                    f"experts per model-axis device)"
                )
        elif 4 % args.model_parallel:
            # TransformerLM's default head count; ring head sharding
            # needs whole heads per model-axis device
            parser.error(
                f"--model-parallel {args.model_parallel} must divide "
                f"the model's 4 attention heads"
            )
    enable_compile_cache()  # the persistent cache, and the compile log
    groups = mdt.setup_groups(args.ngroups, model_parallel=args.model_parallel)
    if args.seq_len % groups[0].data_size:
        parser.error(
            f"--seq-len must divide by {groups[0].data_size} "
            f"(ring devices per {args.ngroups}-group trial)"
        )
    if args.ring_flash:
        from multidisttorch_tpu.ops.pallas_attention import (
            make_ring_flash_attention as make_attn,
        )
    else:
        make_attn = make_ring_attention

    # lr sweep, one trial per submesh (the reference's epochs+group_id
    # knob generalized, SURVEY.md Q7)
    lrs = [1e-3 * (3.0**g) for g in range(args.ngroups)]

    # Shared periodic corpus (data/datasets.py synthetic_corpus):
    # perfectly learnable, so final perplexity ~1 is the correctness
    # signal. Each trial samples its own fixed windows (seeded by
    # group id), so trials see distinct data.
    from multidisttorch_tpu.data import synthetic_corpus

    corpus = synthetic_corpus(
        n=max(65536, 4 * args.seq_len), vocab_size=args.vocab
    )

    trials = []
    for g, lr in zip(groups, lrs):
        if not g.is_local_member:  # multi-host: skip remote submeshes
            continue
        t_admit = time.perf_counter()
        if args.latent_moe:
            from multidisttorch_tpu.models.latent_moe import LatentMoELM

            model = LatentMoELM(
                vocab_size=args.vocab, d_model=args.d_model,
                num_layers=args.layers + 1, max_len=args.seq_len,
                num_experts=args.latent_moe, top_k=2,
                attention=make_attn(g, causal=True),
            )
        elif args.moe:
            from multidisttorch_tpu.models.transformer import MoETransformerLM

            # experts claim the model axis, so heads stay replicated
            model = MoETransformerLM(
                vocab_size=args.vocab, d_model=args.d_model,
                num_layers=args.layers, max_len=args.seq_len,
                num_experts=args.moe,
                attention=make_attn(g, causal=True, shard_heads=False),
            )
        else:
            model = TransformerLM(
                vocab_size=args.vocab, d_model=args.d_model,
                num_layers=args.layers, max_len=args.seq_len,
                attention=make_attn(g, causal=True),
            )
        tx = optax.adam(lr)
        psh = sh = None
        if args.model_parallel > 1:
            from multidisttorch_tpu.models.transformer import (
                moe_lm_ep_shardings,
                transformer_tp_shardings,
            )
            from multidisttorch_tpu.train.steps import state_shardings

            psh = (
                moe_lm_ep_shardings(g, model)
                if args.moe
                else transformer_tp_shardings(g, model)
            )
        rows = corpus.batch(
            np.random.default_rng(g.group_id), args.batch_size, args.seq_len
        )
        state = create_lm_state(
            g, model, tx, jax.random.key(g.group_id),
            example_len=args.seq_len, param_shardings=psh,
        )
        if psh is not None:
            sh = state_shardings(state)
        entry = {
            "trial": g,
            "lr": lr,
            "state": state,
            "eval": make_lm_eval_step(
                g, model, sequence_parallel=True, shardings=sh
            ),
            # g.device_put (not jax.device_put): on a process-
            # spanning submesh each owner feeds only its
            # addressable shards
            "tokens": g.device_put(
                rows,
                g.sharding(None, DATA_AXIS),
            ),
        }
        if args.fused_steps > 1:
            # Production dispatch shape: K steps per host round-trip
            # (the sizing rule from docs/DISPATCH.md). The demo trains
            # on one fixed batch, so the stacked chunk just repeats it.
            entry["step"] = make_lm_multi_step(
                g, model, tx, sequence_parallel=True, shardings=sh
            )
            entry["input"] = g.device_put(
                np.ascontiguousarray(
                    np.broadcast_to(rows, (args.fused_steps,) + rows.shape)
                ),
                lm_chunk_sharding(g, sequence_parallel=True),
            )
        else:
            entry["step"] = make_lm_train_step(
                g, model, tx, sequence_parallel=True, shardings=sh
            )
            entry["input"] = entry["tokens"]
        entry["admit"] = (t_admit, time.perf_counter())
        trials.append(entry)

    kind = "ring-flash" if args.ring_flash else "ring"
    per_dev = args.seq_len // groups[0].data_size
    tp = (
        f" x {args.model_parallel}-way "
        + ("expert" if args.moe else "tensor/head")
        + " parallel"
        if args.model_parallel > 1
        else ""
    )
    mdt.log0(
        f"{len(groups)} concurrent {kind} trials; {args.seq_len} tokens "
        f"({per_dev}/device inside each {groups[0].data_size}-device "
        f"ring){tp}"
    )

    # Cooperative round-robin: one dispatch per trial per cycle (K
    # fused steps each under --fused-steps), no barriers.
    t0 = time.time()
    K = args.fused_steps
    interval = 10
    for i in range(args.steps // K):
        for t in trials:
            t_call = time.perf_counter()
            t["state"], t["m"] = t["step"](t["state"], t["input"])
            if i == 0:
                # Why the trial took this long to start, from the
                # program's compile log (docs/OBSERVABILITY.md,
                # "Admission"): its state's programs, then the step's
                # trace, lowering and load or compile. Waiting for the
                # first loss holds the next trial's first call back,
                # this once.
                jax.block_until_ready(t["m"]["loss"])
                t_ready = time.perf_counter()
                program = t["step"].__name__  # train.lm.STEP_PROGRAM when K == 1
                mdt.log0(
                    admission_line(
                        admission_split(program, *t["admit"]),
                        admission_split(program, t_call, t_ready),
                        t["admit"][1] - t["admit"][0] + t_ready - t_call,
                    ),
                    trial=t["trial"],
                )
        # Log the loss of EVERY step a per-step loop would have logged
        # in this chunk, labeled with that step (the fused metrics come
        # back (K,), so each cadence point is indexable — same contract
        # as hpo/driver.py's fused logging, incl. K > interval).
        first = i * K
        j = -(-first // interval) * interval  # ceil to the cadence
        while j < first + K:
            for t in trials:
                loss = (
                    t["m"]["loss"] if K == 1 else t["m"]["loss"][j - first]
                )
                mdt.log0(
                    f"step {j:4d}  loss {float(loss):.4f}",
                    trial=t["trial"],
                )
            j += interval

    for t in trials:
        if args.latent_moe:
            counts = np.asarray(t["m"]["expert_counts"])
            mdt.log0(
                f"assignments per expert, last step, by layer: "
                f"{counts.reshape(-1, args.latent_moe).tolist()}",
                trial=t["trial"],
            )
        ev = t["eval"](t["state"], t["tokens"])
        mdt.log0(
            f"lr={t['lr']:.0e}: final loss {float(ev['loss']):.4f}, "
            f"perplexity {float(ev['perplexity']):.3f}, "
            f"wall {time.time() - t0:.1f}s",
            trial=t["trial"],
        )


if __name__ == "__main__":
    main()
