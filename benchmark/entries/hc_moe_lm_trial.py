"""How a hyper-connected latent-attention, routed-expert configuration
is driven through the program.

``moe_lm_trial``'s trial path, host loop, order of a run and record
(whose ``_Trial``, ``_Loop``, counter keeping, progress line and
constants are imported, not copied). ``moe_lm_trial.build_model``
passes a fixed list of fields and ``reference_weights`` a fixed tree,
and its ``run`` binds both by name, so what binds the model is written
again here: how ``LatentMoELM`` is built from the file's keys (the
residual streams and the rotary scaling among them), the weights under
the reference's names (each sublayer's connection with them), the
comparison, the FLOPs of a step (``flops_hc``) and ``run``. Beside the
assignments per expert held this entry keeps the step's second counter,
``hc_marginal_err``: the largest distance of a row or column sum of any
``Hres`` from 1, printed over the window and, in the comparison, beside
the reference's own. It gates nothing by a limit of its own.

A reading, the window and the order of a run are ``lm_trial``'s.
"""

from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import compare, flops_hc, readings, trace_reduce
from benchmark.entries import moe_lm_trial
from benchmark.entries.lm_trial import (
    CORPUS_TOKENS, MIN_READINGS, TRACED_SECONDS, WARM_ROUNDS,
    _Loop, _peak_bytes, _start_trace, _Trial,
)
from benchmark.entries.moe_lm_trial import (
    REFERENCE_SEQUENCES, _counting, _say_counts, chosen_experts,
)
from multidisttorch_tpu.data import synthetic_corpus
from multidisttorch_tpu.models.latent_moe import LatentMoELM, YarnScaling
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import make_lm_train_step
from multidisttorch_tpu.train.steps import TrainState

CONNECTIONS = ("hc_attn", "hc_mlp")  # a block's two, by the flax names the reference shares
# The first block's first connection reads four copies of the embedding:
# Hres X is X whatever Hres (its rows sum to 1), and the size of u = (sum
# of Hpre) x is divided out again by ln_attn. The gradient of what makes
# those two maps is zero but for the norms' eps: rounding on both sides.
ZERO_BY_CONSTRUCTION = tuple(
    f"['blocks'][0]['hc_attn']['{kind}_{which}']"
    for kind in ("a", "b", "phi") for which in ("pre", "res")
)


def build_model(config: dict) -> LatentMoELM:
    plain = moe_lm_trial.build_model(config)
    scaling = config["rope_scaling"]
    if scaling["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {scaling['type']!r}: only yarn is known")
    return plain.clone(
        rope_scaling=YarnScaling(
            factor=scaling["factor"],
            original_max_position=scaling["original_max_position_embeddings"],
            beta_fast=scaling["beta_fast"], beta_slow=scaling["beta_slow"],
            mscale=scaling["mscale"], mscale_all_dim=scaling["mscale_all_dim"],
        ),
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]),
    )


def reference_weights(params, config: dict) -> dict:
    """``moe_lm_trial.reference_weights`` with each block's two
    connections beside it, under the names the program gives them."""
    out = moe_lm_trial.reference_weights(params, config)
    for i, block in enumerate(out["blocks"]):
        block.update({name: dict(params[f"block_{i}"][name]) for name in CONNECTIONS})
    return out


def reference_check(cell, group, model, params, tokens) -> dict:
    """``moe_lm_trial.reference_check`` for this entry's model and
    weights, with ``hc_marginal_err`` of the probe step beside the
    reference's."""
    config, tol = cell.config, cell.config["compared"]
    tokens = group.device_put(tokens, group.batch_sharding)

    sys_logits, sys_chosen = jax.jit(
        lambda p, t: chosen_experts(model, p, t, config)
    )(params, tokens)
    sgd = optax.sgd(1.0)
    probe = group.device_put(
        TrainState(
            params=jax.tree.map(jnp.copy, params),
            opt_state=sgd.init(params),
            step=jnp.zeros((), jnp.int32),
        )
    )
    after, metrics = make_lm_train_step(group, model, sgd)(probe, tokens)
    sys_grads = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(params, after.params)
    del after, probe
    # to the host until the reference has run: its float32 program needs
    # 11 of the chip's 15.75 GiB beside the weights (8.3 of temporaries,
    # 3.1 of gradients), and 2.8 GiB of the program's gradients do not fit
    # beside them; they come back leaf by leaf in the comparison
    sys_grads = jax.device_get(sys_grads)

    ref = cell.reference()
    ref_logits, ref_loss, ref_grads, ref_routing = jax.jit(
        lambda w, t: ref.logits_loss_grads(w, t, config)
    )(reference_weights(params, config), tokens)

    by_leaf = compare.tree_rel_l2(reference_weights(sys_grads, config), ref_grads)
    # as moe_lm_trial: a leaf whose gradient is zero on both sides (the
    # selection bias; here also ZERO_BY_CONSTRUCTION) has no relative
    # error, and the routers are judged apart from the leaves that only
    # rounding moves
    norms = {k: float(v) for k, v in compare.tree_rms(ref_grads).items()}
    floor = 1e-3 * statistics.median(norms.values())
    judged = {k: e for k, e in by_leaf.items()
              if norms[k] > floor and not k.endswith(ZERO_BY_CONSTRUCTION)}
    routers = {k: e for k, e in judged.items() if "router" in k}
    # A connection's parameters get the inner products of the streams
    # with their gradients, summed over every token and channel: terms of
    # both signs that nearly cancel, so a gate or a bias (1 to 16
    # numbers) moves by its own size with every choice of expert that
    # differs upstream. The connections are judged as the one vector all
    # their leaves make, which the projections (14,336 x 24 a connection)
    # carry; the worst leaf of each kind is in the note.
    connections = {k: e for k, e in judged.items() if any(name in k for name in CONNECTIONS)}
    others = {k: e for k, e in judged.items() if k not in routers and k not in connections}
    squares = {jax.tree_util.keystr(path): norms[jax.tree_util.keystr(path)] ** 2 * leaf.size
               for path, leaf in jax.tree_util.tree_leaves_with_path(ref_grads)}
    hc_rel_l2 = math.sqrt(
        sum(e * e * squares[k] for k, e in connections.items())
        / sum(squares[k] for k in connections)
    )
    by_kind: dict[str, float] = {}
    for k, e in connections.items():
        kind = k.rsplit("['", 1)[-1].rstrip("']")
        by_kind[kind] = max(e, by_kind.get(kind, 0.0))
    differing = jnp.any(
        jnp.sort(sys_chosen, axis=-1) != jnp.sort(ref_routing["chosen"], axis=-1), axis=-1
    )
    counts = np.asarray(metrics["expert_counts"])
    ref_counts = np.asarray(ref_routing["expert_counts"])
    errors = {
        "logits_rel_rms": float(compare.rel_rms(sys_logits, ref_logits)),
        "loss_rel": abs(float(metrics["loss"]) - float(ref_loss)) / abs(float(ref_loss)),
        "grad_rel_l2": max(others.values()),
        "router_grad_rel_l2": max(routers.values()),
        "hc_grad_rel_l2": hc_rel_l2,
        "routing_diff_share": float(jnp.mean(differing)),
    }
    ok, notes = compare.verdict(errors, tol)
    notes.append(
        f"loss program {float(metrics['loss']):.6f} reference {float(ref_loss):.6f}; "
        f"worst gradient leaves "
        f"{[(k, round(others[k], 4)) for k in sorted(others, key=others.get)[-3:][::-1]]}, routers "
        f"{[round(e, 4) for e in routers.values()]}; {len(judged)} of {len(by_leaf)} leaves judged"
    )
    notes.append(
        f"the connections' {len(connections)} leaves as one vector {hc_rel_l2:.4f}; the worst "
        f"leaf of each kind {({k: round(e, 3) for k, e in sorted(by_kind.items())})}"
    )
    notes.append(
        f"experts chosen differ in {int(jnp.sum(differing))} of {differing.size} (token, layer) "
        f"choices, by layer {np.asarray(jnp.mean(differing, axis=-1)).round(4).tolist()}; "
        f"assignments to the experts held: program {int(counts.sum())} reference "
        f"{int(ref_counts.sum())}, largest difference for one expert "
        f"{int(np.abs(counts - ref_counts).max())}"
    )
    notes.append(
        f"hc_marginal_err program {float(metrics['hc_marginal_err']):.3e} reference "
        f"{float(ref_routing['hc_marginal_err']):.3e}"
    )
    return {"ok": ok, "errors": errors, "notes": notes}


def _keeping_marginal_err(trial: _Trial) -> list:
    """As ``moe_lm_trial._counting`` keeps a step's assignments: hand
    ``metrics["hc_marginal_err"]`` to the list returned, still on the
    device."""
    kept: list = []
    step = trial.step

    def keeping_step(state, tokens):
        state, metrics = step(state, tokens)
        kept.append(metrics["hc_marginal_err"])
        return state, metrics

    trial.step = keeping_step
    return kept


def run(cell, devices, seed: int, seconds: float, trace_dir, book) -> dict:
    """One run of one cell: ``moe_lm_trial.run``'s order and record,
    plus ``hc_marginal_err`` ``(steps of the window,)``."""
    config, traffic = cell.config, cell.traffic
    n = len(traffic["learning_rates"])  # one trial per learning rate, one chip each
    spans: dict[str, float] = {}

    def span(name: str, t0: float) -> float:
        spans[name] = spans.get(name, 0.0) + time.perf_counter() - t0
        return time.perf_counter()

    t = time.perf_counter()
    corpus = synthetic_corpus(
        n=max(CORPUS_TOKENS, 4 * traffic["sequence_length"]),
        vocab_size=config["vocab_size"],
        seed=seed,
    )
    t = span("corpus_s", t)
    groups = setup_groups(n, devices=list(devices)[:n])
    model = build_model(config)
    trials = [
        _Trial(g, model, lr, i, seed, traffic, corpus)
        for i, (g, lr) in enumerate(zip(groups, traffic["learning_rates"], strict=True))
    ]
    counters = [(_counting(tr), _keeping_marginal_err(tr)) for tr in trials]
    t = span("model_build_s", t)
    for tr in trials:
        tr.init_state()
    jax.block_until_ready([tr.state for tr in trials])
    t = span("state_init_s", t)
    loop = _Loop(trials)
    loop.in_flight = loop.dispatch_round()
    for _ in range(WARM_ROUNDS - 1):
        loop.advance()
    stamps = [loop.advance()]  # ends the warm rounds, opens the window
    span("step_ready_s", t)
    for tr in trials:
        tr.losses.clear()
    loop.input_s.clear()
    # the step in flight is the window's first; the warm rounds' counters go
    for pair in counters:
        for kept in pair:
            del kept[:-1]
    compile_at_open = book.snapshot()

    while readings.window_open(stamps[-1] - stamps[0], len(stamps) - 1, seconds, MIN_READINGS):
        stamps.append(loop.advance())
    compile_at_close = book.snapshot()
    window_losses = [list(tr.losses) for tr in trials]
    window_counts = [pair[0][: len(tr.losses)] for pair, tr in zip(counters, trials)]
    window_errs = [pair[1][: len(tr.losses)] for pair, tr in zip(counters, trials)]
    window_input_s = list(loop.input_s)
    peak_bytes = _peak_bytes(devices[:n])

    trace = None
    if trace_dir is not None:
        _start_trace(trace_dir)
        loop.advance()  # refill the queue after the profiler's start
        traced_rounds = math.ceil(TRACED_SECONDS / statistics.median(readings.intervals(stamps)))
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(traced_rounds):
                loop.advance()
        loop.drain()
        jax.profiler.stop_trace()
        trace = trace_reduce.reduce_trace(trace_dir)
    else:
        loop.drain()

    # The comparison with the plain reference comes last, on trial 0's
    # weights made again from the same key (see ``lm_trial.run``).
    t = time.perf_counter()
    for tr in trials:
        tr.state = None
    expert_counts = np.array(jax.device_get(window_counts), np.int64)  # (trials, steps, L, held)
    marginal_errs = np.array(jax.device_get(window_errs), np.float64)  # (trials, steps)
    for pair in counters:
        for kept in pair:
            kept.clear()
    tokens_per_step = traffic["batch_sequences"] * traffic["sequence_length"]
    _say_counts(expert_counts[0], config, tokens_per_step)
    print(
        f"[benchmark] hc_marginal_err (largest distance of a row or column sum of any Hres "
        f"from 1) over the window: first step {marginal_errs[0, 0]:.3e} last step "
        f"{marginal_errs[0, -1]:.3e} max {marginal_errs[0].max():.3e}",
        flush=True,
    )
    trials[0].init_state()
    params, trials[0].state = trials[0].state.params, None  # the moments are not needed
    sample = corpus.batch(
        np.random.default_rng([seed, 10**6]),
        REFERENCE_SEQUENCES,
        traffic["sequence_length"],
    )
    reference = reference_check(cell, groups[0], model, params, sample)
    span("reference_check_s", t)

    losses = np.array(jax.device_get(window_losses), np.float64)  # (trials, steps)
    finite = np.isfinite(losses)
    k = max(1, losses.shape[1] // 8)
    falling = bool(
        np.all(np.median(losses[:, -k:], axis=1) < np.median(losses[:, :k], axis=1))
    )
    compiles_in_window = sum(
        compile_at_close[key] - compile_at_open[key] for key in ("hits", "misses")
    )
    checks = {
        "reference": reference["ok"],
        "losses_finite": bool(finite.all()),
        "losses_falling": falling,
        "nothing_compiled_in_window": compiles_in_window == 0,
    }
    return {
        "spans": spans,
        "stamps": stamps,
        "min_readings": MIN_READINGS,
        "units_per_reading_per_chip": tokens_per_step,
        "flops_per_unit": flops_hc.train_flops_per_token(
            config,
            traffic["sequence_length"],
            float(expert_counts.sum(axis=-1).mean()) / tokens_per_step,
        ),
        "expert_counts": expert_counts[0],
        "hc_marginal_err": marginal_errs[0],
        "config": config,
        "sequence_length": traffic["sequence_length"],
        "input_s": window_input_s,
        "compile_setup": compile_at_open,
        "compiles_in_window": compiles_in_window,
        "peak_bytes": peak_bytes,
        "peak_bytes_at_end": _peak_bytes(devices[:n]),
        "trace": trace,
        "reference": reference,
        "losses_first_last": [[float(r[0]), float(r[-1])] for r in losses],
        "attempted": int(losses.size),
        "failed": int((~finite).sum()),
        "checks": checks,
        "correct": all(checks.values()),
    }
