"""How a gated short-convolution / attention expert configuration
(``ShortConvMoELM``) is driven through the program.

``moe_lm_trial``'s trial path, host loop, order of a run and record
(whose ``_Trial``, ``_Loop``, counter keeping, progress line and
constants are imported, not copied). The four expert and hybrid entries
before this one bind their model builder, their weights' renaming and
their FLOPs by name, so what binds the model is written again here: how
``ShortConvMoELM`` is built from the file's keys, the weights under the
reference's names, the comparison (the program's side is the timed step
itself, its gradients read back from Adam's first moment and the
parameters it left held against Adam's step on them, as
``swa_moe_lm_trial`` and ``ssm_lm_trial`` do; they wait on the host
while the float32 reference holds the chip), the FLOPs of a step
(``flops_lfm2``) and ``run``.

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

from benchmark import compare, flops_lfm2, readings, trace_reduce
from benchmark.entries.lm_trial import (
    CORPUS_TOKENS, DTYPES, MIN_READINGS, TRACED_SECONDS, WARM_ROUNDS,
    _Loop, _peak_bytes, _start_trace, _Trial,
)
from benchmark.entries.moe_lm_trial import _counting, _say_counts
from multidisttorch_tpu.data import synthetic_corpus
from multidisttorch_tpu.models.conv_moe import ShortConvMoELM
from multidisttorch_tpu.parallel.mesh import setup_groups


def build_model(config: dict) -> ShortConvMoELM:
    assumed = config["assumed"]
    return ShortConvMoELM(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        dense_hidden_dim=config["intermediate_size"],
        conv_taps=config["conv_L_cache"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        hidden_dim=config["moe_intermediate_size"],
        num_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        absent_share_grad=assumed["absent_share_grad"],
        eps=config["norm_eps"],
        max_len=config["max_position_embeddings"],
        tie_embeddings=assumed["tie_word_embeddings"],
        embed_stddev=assumed["embedding_stddev"],
        dtype=DTYPES[assumed["compute_dtype"]],
        remat=assumed["remat"],
    )


def reference_weights(params, config: dict) -> dict:
    """The program's parameter tree under the reference's names. No
    array is copied or reshaped: flax stores a matrix ``(in, out)`` as
    the reference does. Gradients go through the same renaming."""
    kernel = lambda p, name: p[name]["kernel"]

    def block(p, kind):
        out = {"ln1": p["ln_attn"]["scale"], "ln2": p["ln_mlp"]["scale"]}
        if kind == "conv":
            out |= {"w_in": kernel(p, "in_proj"), "conv_w": p["conv_w"],
                    "w_out": kernel(p, "out_proj")}
        else:
            out |= {"wq": kernel(p, "q"), "wk": kernel(p, "k"), "wv": kernel(p, "v"),
                    "wo": kernel(p, "proj"),
                    "q_norm": p["q_norm"]["scale"], "k_norm": p["k_norm"]["scale"]}
        if "moe" not in p:
            return out | {"w_gate": kernel(p, "gate"), "w_up": kernel(p, "up"),
                          "w_down": kernel(p, "down")}
        m = p["moe"]
        return out | {"router": m["router"], "router_bias": m["score_bias"],
                      "e_gate": m["w_gate"], "e_up": m["w_up"], "e_down": m["w_down"]}

    return {
        "wte": params["tok_embed"]["embedding"],
        "blocks": [block(params[f"block_{i}"], kind)
                   for i, kind in enumerate(config["layer_types"])],
        "lnf": params["ln_out"]["scale"],
    }


def chosen_experts(model, params, tokens, config: dict):
    """``(logits, chosen)`` of ``model.apply``: the experts each token
    chose in each expert layer, ``(expert layers, tokens, k)``, as the
    expert layer sows them."""
    (logits, _), state = model.apply({"params": params}, tokens, mutable=["intermediates"])
    layers = range(config["num_dense_layers"], config["num_hidden_layers"])
    return logits, jnp.stack(
        [state["intermediates"][f"block_{i}"]["moe"]["chosen"][0] for i in layers]
    )


ADAM_B1 = 0.9  # optax.adam's default, which ``lm_trial._Trial`` takes
CONV_LEAVES = ("w_in", "conv_w", "w_out")  # the reference's names
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")


def program_side(trial: _Trial, tokens, config: dict):
    """``(logits, experts chosen, gradients, the parameters' change,
    the step's metrics)`` of the trial on the seed's initial weights,
    all but the choices on the host: ``ShortConvMoELM.apply`` for the
    logits and the choices, and **the timed step**, the trial's own
    ``make_lm_train_step`` under its own Adam on a state made again
    from the trial's key, for the loss, the counter, the gradients
    (Adam's first moment after one step from zero is ``(1 - b1) g``)
    and what it added to the parameters."""
    model = trial.model
    trial.init_state()
    state, trial.state = trial.state, None
    logits, chosen = jax.jit(
        lambda p, t: chosen_experts(model, p, t, config)
    )(state.params, tokens)
    logits = jax.device_get(logits)
    before = jax.device_get(state.params)
    after, metrics = trial.step(state, tokens)  # donates the state
    grads = jax.tree.map(
        lambda mu: mu / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")),
    )
    moved = jax.tree.map(np.subtract, jax.device_get(after.params), before)
    return logits, chosen, grads, moved, jax.device_get(metrics)


def reference_check(cell, trial: _Trial, tokens) -> dict:
    """The program against the configuration's plain reference, on the
    seeded initial weights and ``tokens``, the traffic's own batch: the
    executable compared is the one the window ran. The program's
    arrays wait on the host while the float32 reference holds the
    chip."""
    config, tol = cell.config, cell.config["compared"]
    tokens = trial.group.device_put(tokens, trial.group.batch_sharding)
    sys_logits, sys_chosen, sys_grads, sys_moved, metrics = program_side(trial, tokens, config)
    trial.init_state()  # the step consumed the weights: the same key makes them again
    params, trial.state = trial.state.params, None

    ref = cell.reference()
    weights = reference_weights(params, config)
    hidden, ref_loss, ref_grads, ref_routing = jax.jit(
        lambda w, t: ref.hidden_loss_grads(w, t, config)
    )(weights, tokens)
    del params
    sys_grads, sys_moved = reference_weights(sys_grads, config), reference_weights(sys_moved, config)
    by_leaf = compare.tree_rel_l2(sys_grads, ref_grads)
    # The selection bias moves which experts are chosen and not their
    # weights: its gradient is zero on both sides, Adam leaves it where
    # it was, and a zero has no relative error (moe_lm_trial's floor).
    norms = {k: float(v) for k, v in compare.tree_rms(ref_grads).items()}
    floor = 1e-3 * statistics.median(norms.values())
    judged = {k: e for k, e in by_leaf.items() if norms[k] > floor}
    del ref_grads
    # The parameters' change against Adam's step on the gradients the step
    # holds, which ``judged`` has just held to the reference's: a step that
    # leaves the parameters as they were reads 1 (ssm_lm_trial's way).
    own_moved = jax.jit(
        lambda g: ref.adam_first_step(g, cell.traffic["learning_rates"][0])
    )(sys_grads)
    del sys_grads
    moved = {k: e for k, e in compare.tree_rel_l2(sys_moved, own_moved).items() if k in judged}
    del own_moved, sys_moved
    ref_logits = jax.jit(lambda x, w: ref.logits_of(x, w, config))(hidden, weights)
    del weights, hidden

    # Every judged leaf is held, by the worst of its kind: the routers
    # apart (a token whose experts differ between bf16 and float32 moves
    # its router's gradient most), the conv operators' three leaves and
    # the attention's six apart from the rest too, which no choice of
    # expert reaches but through the residual: there a wrong tap, gate,
    # head norm, rotation or dQ, dK, dV of the 64-wide kernels would show.
    routers = {k: e for k, e in judged.items() if k.endswith("['router']")}
    others = {k: e for k, e in judged.items() if k not in routers}
    worst_of = lambda names: {
        name: max(e for k, e in others.items() if k.endswith(f"['{name}']")) for name in names
    }
    conv, attention = worst_of(CONV_LEAVES), worst_of(ATTENTION_LEAVES)
    differing = jnp.any(
        jnp.sort(sys_chosen, axis=-1) != jnp.sort(ref_routing["chosen"], axis=-1), axis=-1
    )
    counts = np.asarray(metrics["expert_counts"])
    ref_counts = np.asarray(ref_routing["expert_counts"])
    errors = {
        "logits_rel_rms": float(compare.rel_rms(sys_logits, ref_logits)),
        "loss_rel": abs(float(metrics["loss"]) - float(ref_loss)) / abs(float(ref_loss)),
        "grad_rel_l2": max(others.values()),
        "conv_grad_rel_l2": max(conv.values()),
        "attn_grad_rel_l2": max(attention.values()),
        "router_grad_rel_l2": max(routers.values()),
        "routing_diff_share": float(jnp.mean(differing)),
        "param_change_rel_l2": max(moved.values()),
    }
    ok, notes = compare.verdict(errors, tol)
    notes.append(
        f"loss program {float(metrics['loss']):.6f} reference {float(ref_loss):.6f}; "
        f"worst gradient leaves "
        f"{[(k, round(others[k], 4)) for k in sorted(others, key=others.get)[-3:][::-1]]}, routers "
        f"{[round(e, 4) for e in routers.values()]}, the conv operators' worst by name "
        f"{ {name: round(e, 4) for name, e in conv.items()} }, the attention's "
        f"{ {name: round(e, 4) for name, e in attention.items()} }; "
        f"{len(judged)} of {len(by_leaf)} leaves judged; the parameters' change, worst leaves "
        f"{[(k, float(f'{moved[k]:.3g}')) for k in sorted(moved, key=moved.get)[-2:][::-1]]}"
    )
    notes.append(
        f"experts chosen differ in {int(jnp.sum(differing))} of {differing.size} (token, layer) "
        f"choices, by layer {np.asarray(jnp.mean(differing, axis=-1)).round(4).tolist()}; "
        f"assignments to the experts held: program {int(counts.sum())} reference "
        f"{int(ref_counts.sum())}, largest difference for one expert "
        f"{int(np.abs(counts - ref_counts).max())}"
    )
    return {"ok": ok, "errors": errors, "notes": notes}


def run(cell, devices, seed: int, seconds: float, trace_dir, book) -> dict:
    """One run of one cell: ``moe_lm_trial.run``'s order and record."""
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
    # (steps, layers); a layer that sent the experts held nothing reads 0
    fullest = expert_counts[0].max(axis=-1) / np.maximum(expert_counts[0].mean(axis=-1), 1e-9)
    print(
        f"[benchmark] fullest expert held over the mean of those held, the worst layer of a "
        f"step: median over the window {statistics.median(fullest.max(axis=-1)):.3f}, at the "
        f"window's last step {fullest[-1].max():.3f} (by layer {fullest[-1].round(3).tolist()})",
        flush=True,
    )
    sample = corpus.batch(
        np.random.default_rng([seed, 10**6]),
        traffic["batch_sequences"],  # the timed step's own shape
        traffic["sequence_length"],
    )
    reference = reference_check(cell, trials[0], sample)
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
        "flops_per_unit": flops_lfm2.train_flops_per_token(
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
