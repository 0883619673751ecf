"""How a looped language model (``LoopedLM``: a stack of blocks run
several times a token, an exit gate after every pass) is driven through
the program.

``ssm_lm_trial``'s trial path, host loop, order of a run and record
(``lm_trial``'s ``_Trial``, ``_Loop``, trace handling and constants are
imported, not copied). The entries bind their model builder, their
weights' renaming and their FLOPs by name, so what binds the model is
written again here: how ``LoopedLM`` is built from the file's keys, the
weights under the reference's names, the comparison (the program's side
is the timed step itself, its gradients read back from Adam's first
moment and the parameters it left held against Adam's step on the
reference's gradients; they wait on the host while the float32
reference holds the chip), the FLOPs of a step (``flops_ouro``) and
``run``. The step's two counters, ``exit_p`` and ``loop_loss``, are
compared with the reference's.

A reading, the window and the order of a run are ``lm_trial``'s.
"""

from __future__ import annotations

import math
import statistics
import time

import jax
import numpy as np
import optax

from benchmark import compare, flops_ouro, readings, trace_reduce
from benchmark.entries.lm_trial import (
    CORPUS_TOKENS, DTYPES, MIN_READINGS, TRACED_SECONDS, WARM_ROUNDS,
    _Loop, _peak_bytes, _start_trace, _Trial,
)
from multidisttorch_tpu.data import synthetic_corpus
from multidisttorch_tpu.models.looped import LoopedLM
from multidisttorch_tpu.parallel.mesh import setup_groups


def build_model(config: dict) -> LoopedLM:
    assumed = config["assumed"]
    return LoopedLM(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        loops=config["total_ut_steps"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        hidden_dim=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        exit_entropy_weight=assumed["exit_entropy_weight"],
        eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=DTYPES[assumed["compute_dtype"]],
        remat=assumed["remat"],
    )


def reference_weights(params) -> dict:
    """The program's parameter tree under the reference's names. No
    array is copied or reshaped: flax stores a matrix ``(in, out)`` as
    the reference does. Gradients go through the same renaming."""
    kernel = lambda p, name: p[name]["kernel"]
    scale = lambda p, name: p[name]["scale"]

    def block(p):
        return {
            "ln1": scale(p, "ln_attn"), "wq": kernel(p, "q"), "wk": kernel(p, "k"),
            "wv": kernel(p, "v"), "wo": kernel(p, "proj"), "ln1_post": scale(p, "ln_attn_out"),
            "ln2": scale(p, "ln_mlp"), "w_gate": kernel(p, "gate"), "w_up": kernel(p, "up"),
            "w_down": kernel(p, "down"), "ln2_post": scale(p, "ln_mlp_out"),
        }

    blocks = sorted((k for k in params if k.startswith("block_")), key=lambda k: int(k[6:]))
    return {
        "wte": params["tok_embed"]["embedding"],
        "blocks": [block(params[k]) for k in blocks],
        "lnf": params["ln_out"]["scale"],
        "gate_w": params["exit_gate"]["kernel"], "gate_b": params["exit_gate"]["bias"],
        "head": params["head"]["kernel"],
    }


ADAM_B1 = 0.9  # optax.adam's default, which ``lm_trial._Trial`` takes
GATE_LEAVES = ("gate_w", "gate_b")  # the reference's names


def program_side(trial: _Trial, tokens):
    """``(U logits (B, T, V), gradients, the parameters' change, the
    step's metrics)`` of the trial on the seed's initial weights, all on
    the host: **the timed step**, the trial's own ``make_lm_train_step``
    under its own Adam on a state made again from the trial's key, for
    the loss, the counters, the gradients (Adam's first moment after one
    step from zero is ``(1 - b1) g``) and what it added to the
    parameters; then ``LoopedLM.apply`` with its head, on the same
    initial weights, for every loop's logits."""
    model = trial.model
    trial.init_state()
    state, trial.state = trial.state, None
    before = jax.device_get(state.params)
    after, metrics = trial.step(state, tokens)  # donates the state
    grads = jax.tree.map(
        lambda mu: mu / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")),
    )
    moved = jax.tree.map(np.subtract, jax.device_get(after.params), before)
    del after
    # The four loops' logits are 6 GiB at 2 x 4,096 tokens: made once the
    # step's state is gone, from the initial weights alone, by the model's
    # own head (float32, as a caller of the model gets them).
    logits = jax.device_get(
        jax.jit(lambda p, t: model.apply({"params": p}, t)[0])(before, tokens)
    )
    return logits, grads, moved, jax.device_get(metrics)


def reference_check(cell, trial: _Trial, tokens) -> dict:
    """The program against the configuration's plain reference, on the
    seeded initial weights and ``tokens``, the traffic's own batch: the
    executable compared is the one the window ran."""
    config, tol = cell.config, cell.config["compared"]
    tokens = trial.group.device_put(tokens, trial.group.batch_sharding)
    sys_logits, sys_grads, sys_moved, metrics = program_side(trial, tokens)
    trial.init_state()  # the step consumed the weights: the same key makes them again
    params, trial.state = trial.state.params, None

    ref = cell.reference()
    weights = reference_weights(params)
    states, ref_loss, ref_grads, ref_counters = jax.jit(
        lambda w, t: ref.hidden_loss_grads(w, t, config)
    )(weights, tokens)
    del params
    sys_grads, sys_moved = reference_weights(sys_grads), reference_weights(sys_moved)
    by_leaf = compare.tree_rel_l2(sys_grads, ref_grads)
    # The parameters' change against Adam's first step on the reference's
    # gradients: a step that leaves the parameters as they were reads 1.
    ref_moved = jax.jit(lambda g: ref.adam_first_step(g, cell.traffic["learning_rates"][0]))(
        ref_grads
    )
    del ref_grads, sys_grads
    moved = compare.tree_rel_l2(sys_moved, ref_moved)
    del ref_moved, sys_moved
    # one loop's logits at a time: 1.5 GiB each at 2 x 4,096 tokens
    logits_of = jax.jit(ref.logits_of)
    by_loop = [
        float(compare.rel_rms(sys_logits[u], logits_of(states[u], weights)))
        for u in range(len(sys_logits))
    ]
    del weights, states, sys_logits

    # the gate's two leaves apart from the rest: a wrong exit distribution,
    # entropy or weighting of the loops' losses would show there first
    gate = {name: max(e for k, e in by_leaf.items() if k.endswith(f"['{name}']"))
            for name in GATE_LEAVES}
    others = {k: e for k, e in by_leaf.items() if not k.endswith(tuple(f"['{n}']" for n in gate))}
    rel = lambda got, want: float(np.max(np.abs(np.asarray(got) - np.asarray(want))
                                         / np.abs(np.asarray(want))))
    errors = {
        "logits_rel_rms": max(by_loop),
        "loss_rel": abs(float(metrics["loss"]) - float(ref_loss)) / abs(float(ref_loss)),
        "exit_p_rel": rel(metrics["exit_p"], ref_counters["exit_p"]),
        "loop_loss_rel": rel(metrics["loop_loss"], ref_counters["loop_loss"]),
        "grad_rel_l2": max(others.values()),
        "gate_grad_rel_l2": max(gate.values()),
        "param_change_rel_l2": max(moved.values()),
    }
    ok, notes = compare.verdict(errors, tol)
    worst = lambda errs: [(k, float(f"{errs[k]:.3g}")) for k in sorted(errs, key=errs.get)[-3:][::-1]]
    notes.append(
        f"loss program {float(metrics['loss']):.6f} reference {float(ref_loss):.6f}; logits by "
        f"loop {[round(e, 5) for e in by_loop]}; worst gradient leaves {worst(others)}; the "
        f"gate's { {name: round(e, 4) for name, e in gate.items()} }; {len(by_leaf)} leaves "
        f"judged; the parameters' change, worst leaves {worst(moved)}"
    )
    notes.append(
        f"exit_p program {np.asarray(metrics['exit_p']).round(6).tolist()} reference "
        f"{np.asarray(ref_counters['exit_p']).round(6).tolist()}; loop_loss program "
        f"{np.asarray(metrics['loop_loss']).round(6).tolist()} reference "
        f"{np.asarray(ref_counters['loop_loss']).round(6).tolist()}"
    )
    return {"ok": ok, "errors": errors, "notes": notes}


def run(cell, devices, seed: int, seconds: float, trace_dir, book) -> dict:
    """One run of one cell: ``ssm_lm_trial.run``'s order and record."""
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
    compile_at_open = book.snapshot()

    while readings.window_open(stamps[-1] - stamps[0], len(stamps) - 1, seconds, MIN_READINGS):
        stamps.append(loop.advance())
    compile_at_close = book.snapshot()
    window_losses = [list(tr.losses) for tr in trials]
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
    tokens_per_step = traffic["batch_sequences"] * traffic["sequence_length"]
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
        "flops_per_unit": flops_ouro.train_flops_per_token(config, traffic["sequence_length"]),
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
