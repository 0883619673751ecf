"""How a latent-attention, routed-expert configuration is driven
through the program.

The same trial path and the same host loop as ``lm_trial`` (whose
``_Trial``, ``_Loop``, trace handling and constants are imported, not
copied): ``setup_groups`` carves one single-chip submesh per trial, the
trial is a ``LatentMoELM`` with a state from ``create_lm_state`` and a
step from ``make_lm_train_step``, one optimizer step per round on a
fresh batch from ``synthetic_corpus(seed)``. What this entry has of its
own is what the configuration differs in: how the model is built from
the file's keys, the weights under the reference's names, the
comparison (it also compares the experts chosen), the FLOPs of a step
(``flops_joyai``: the routed part from the assignments the step
counted) and the step's counter of assignments per expert held, which
it keeps beside the losses and reads once, after the window.

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

from benchmark import compare, flops_joyai, readings, trace_reduce
from benchmark.entries.lm_trial import (
    CORPUS_TOKENS, DTYPES, MIN_READINGS, TRACED_SECONDS, WARM_ROUNDS,
    _Loop, _peak_bytes, _start_trace, _Trial,
)
from multidisttorch_tpu.data import synthetic_corpus
from multidisttorch_tpu.models.latent_moe import LatentMoELM
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import make_lm_train_step
from multidisttorch_tpu.train.steps import TrainState

REFERENCE_SEQUENCES = 1  # what the comparison with the reference runs on


def build_model(config: dict) -> LatentMoELM:
    return LatentMoELM(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        dense_hidden_dim=config["intermediate_size"],
        num_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_hidden_dim=config["moe_intermediate_size"],
        shared_experts=config["n_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=DTYPES[config["assumed"]["compute_dtype"]],
        remat=config["assumed"]["remat"],
    )


def reference_weights(params, config: dict) -> dict:
    """The program's parameter tree under the reference's names. No
    array is copied or reshaped: flax stores a matrix ``(in, out)`` as
    the reference does. Gradients go through the same renaming."""

    def block(p):
        out = {
            "ln1": p["ln_attn"]["scale"],
            "w_qa": p["q_a"]["kernel"], "q_norm": p["q_norm"]["scale"],
            "w_qb": p["q_b"]["kernel"],
            "w_kva": p["kv_a"]["kernel"], "kv_norm": p["kv_norm"]["scale"],
            "w_kvb": p["kv_b"]["kernel"],
            "wo": p["proj"]["kernel"],
            "ln2": p["ln_mlp"]["scale"],
        }
        if "moe" not in p:
            return out | {
                "w_gate": p["gate"]["kernel"], "w_up": p["up"]["kernel"],
                "w_down": p["down"]["kernel"],
            }
        m = p["moe"]
        return out | {
            "router": m["router"], "score_bias": m["score_bias"],
            "e_gate": m["w_gate"], "e_up": m["w_up"], "e_down": m["w_down"],
            "s_gate": m["shared_gate"]["kernel"], "s_up": m["shared_up"]["kernel"],
            "s_down": m["shared_down"]["kernel"],
        }

    return {
        "wte": params["tok_embed"]["embedding"],
        "blocks": [block(params[f"block_{i}"]) for i in range(config["num_hidden_layers"])],
        "lnf": params["ln_out"]["scale"],
        "head": params["head"]["kernel"],
    }


def chosen_experts(model, params, tokens, config: dict):
    """``(logits, chosen)`` of ``model.apply``: the experts each token
    chose in each expert layer, ``(expert layers, tokens, k)``, as the
    expert layer sows them."""
    (logits, _), state = model.apply({"params": params}, tokens, mutable=["intermediates"])
    layers = range(config["first_k_dense_replace"], config["num_hidden_layers"])
    return logits, jnp.stack(
        [state["intermediates"][f"block_{i}"]["moe"]["chosen"][0] for i in layers]
    )


def reference_check(cell, group, model, params, tokens) -> dict:
    """The program against the configuration's plain reference, on the
    seeded initial weights and ``tokens``. The program's side is what a
    trial runs: ``LatentMoELM.apply`` for the logits and the experts
    chosen, and one step of ``make_lm_train_step`` under
    ``optax.sgd(1.0)``, whose parameter change is the gradient, for the
    loss, the gradients and the counter."""
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

    ref = cell.reference()
    ref_logits, ref_loss, ref_grads, ref_routing = jax.jit(
        lambda w, t: ref.logits_loss_grads(w, t, config)
    )(reference_weights(params, config), tokens)

    by_leaf = compare.tree_rel_l2(reference_weights(sys_grads, config), ref_grads)
    # The selection bias moves which experts are chosen and not their
    # weights: its gradient is zero on both sides, and a zero has no
    # relative error to speak of. The same floor as ``lm_trial``'s.
    norms = {k: float(v) for k, v in compare.tree_rms(ref_grads).items()}
    floor = 1e-3 * statistics.median(norms.values())
    judged = {k: e for k, e in by_leaf.items() if norms[k] > floor}
    # A router's gradient is a sum over the experts each token chose, so
    # it moves with every choice that differs; the routers are judged
    # apart from the leaves that only rounding moves.
    routers = {k: e for k, e in judged.items() if "router" in k}
    others = {k: e for k, e in judged.items() if k not in routers}
    # a (token, layer) choice differs when the two sets of k experts do
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
        "routing_diff_share": float(jnp.mean(differing)),
    }
    ok, notes = compare.verdict(errors, tol)
    notes.append(
        f"loss program {float(metrics['loss']):.6f} reference {float(ref_loss):.6f}; "
        f"worst gradient leaves "
        f"{[(k, round(others[k], 4)) for k in sorted(others, key=others.get)[-3:][::-1]]}, routers "
        f"{[round(e, 4) for e in routers.values()]}; "
        f"{len(judged)} of {len(by_leaf)} leaves judged"
    )
    notes.append(
        f"experts chosen differ in {int(jnp.sum(differing))} of {differing.size} (token, layer) "
        f"choices, by layer {np.asarray(jnp.mean(differing, axis=-1)).round(4).tolist()}; assignments to the experts held: program {int(counts.sum())} reference "
        f"{int(ref_counts.sum())}, largest difference for one expert "
        f"{int(np.abs(counts - ref_counts).max())}"
    )
    return {"ok": ok, "errors": errors, "notes": notes}


def _counting(trial: _Trial) -> list:
    """Keep each step's counter beside its loss: ``trial.step`` hands
    ``metrics["expert_counts"]`` to the list returned, still on the
    device, so nothing waits for it."""
    kept: list = []
    step = trial.step

    def counting_step(state, tokens):
        state, metrics = step(state, tokens)
        kept.append(metrics["expert_counts"])
        return state, metrics

    trial.step = counting_step
    return kept


def _say_counts(counts: np.ndarray, config: dict, tokens_per_step: int) -> None:
    """A progress line on the window's routing: ``counts`` is ``(steps,
    expert layers, experts held)``."""
    per_layer = counts.sum(axis=-1)  # (steps, layers)
    mean = tokens_per_step * config["num_experts_per_tok"] * config["experts_held"][1] \
        / config["router_width"]
    print(
        f"[benchmark] assignments to the experts held, per layer and step: expected {mean:.0f}; "
        f"first step {per_layer[0].tolist()} last step {per_layer[-1].tolist()}; over the "
        f"window min {int(per_layer.min())} median {int(np.median(per_layer))} max "
        f"{int(per_layer.max())}; (step, layer) pairs past twice the expected "
        f"{int((per_layer > 2 * mean).sum())} of {per_layer.size}; fullest expert over the mean, "
        f"first and last step {(counts[0].max(-1) / counts[0].mean(-1)).round(2).tolist()} "
        f"{(counts[-1].max(-1) / counts[-1].mean(-1)).round(2).tolist()}",
        flush=True,
    )


def run(cell, devices, seed: int, seconds: float, trace_dir, book) -> dict:
    """One run of one cell: ``lm_trial.run``'s order and record, plus
    ``expert_counts`` ``(steps of the window, expert layers, experts
    held)`` and the config, for the readers that count work."""
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
    counters = [_counting(tr) for tr in trials]
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
    for kept in counters:
        del kept[:-1]
    compile_at_open = book.snapshot()

    while readings.window_open(stamps[-1] - stamps[0], len(stamps) - 1, seconds, MIN_READINGS):
        stamps.append(loop.advance())
    compile_at_close = book.snapshot()
    window_losses = [list(tr.losses) for tr in trials]
    window_counts = [kept[: len(tr.losses)] for kept, tr in zip(counters, trials)]
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
    for kept in counters:
        kept.clear()
    tokens_per_step = traffic["batch_sequences"] * traffic["sequence_length"]
    _say_counts(expert_counts[0], config, tokens_per_step)
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
        "flops_per_unit": flops_joyai.train_flops_per_token(
            config,
            traffic["sequence_length"],
            float(expert_counts.sum(axis=-1).mean()) / tokens_per_step,
        ),
        "expert_counts": expert_counts[0],
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
