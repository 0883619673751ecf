"""How a state-space / attention hybrid with shared memory and shared
k, v (``SambaYLM``) is driven through the program.

``moe_lm_trial``'s trial path, host loop, order of a run and record
(``lm_trial``'s ``_Trial``, ``_Loop``, trace handling and constants are
imported, not copied). The three expert entries bind their model
builder, their weights' renaming and their FLOPs by name, so what binds
the model is written again here: how ``SambaYLM`` is built from the
file's keys, the weights under the reference's names, the comparison
(the program's side is the timed step itself, its gradients read back
from Adam's first moment, as ``swa_moe_lm_trial`` does, and the
parameters it left held against Adam's step on the reference's
gradients; they wait on the host while the float32 reference holds the
chip), the FLOPs of a step
(``flops_phi4flash``) and ``run``. There is no routing to count: the
step's one counter is ``ssm_state_rms``, compared with the reference's.

A reading, the window and the order of a run are ``lm_trial``'s.
"""

from __future__ import annotations

import math
import statistics
import time

import jax
import numpy as np
import optax

from benchmark import compare, flops_phi4flash, readings, trace_reduce
from benchmark.entries.lm_trial import (
    CORPUS_TOKENS, DTYPES, MIN_READINGS, TRACED_SECONDS, WARM_ROUNDS,
    _Loop, _peak_bytes, _start_trace, _Trial,
)
from multidisttorch_tpu.data import synthetic_corpus
from multidisttorch_tpu.models.ssm_hybrid import SambaYLM
from multidisttorch_tpu.parallel.mesh import setup_groups


def build_model(config: dict) -> SambaYLM:
    assumed = config["assumed"]
    return SambaYLM(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        mb_per_layer=config["mb_per_layer"],
        layer_kinds=tuple(config["layer_kinds"]),
        window=config["sliding_window"],
        mlp_width=config["intermediate_size"],
        d_state=assumed["d_state"],
        d_conv=assumed["d_conv"],
        expand=assumed["expand"],
        dt_rank=assumed["dt_rank"],
        eps=config["layer_norm_eps"],
        max_len=config["max_position_embeddings"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=DTYPES[assumed["compute_dtype"]],
        remat=assumed["remat"],
    )


def reference_weights(params, config: dict) -> dict:
    """The program's parameter tree under the reference's names. No
    array is copied or reshaped: flax stores a matrix ``(in, out)`` as
    the reference does. Gradients go through the same renaming."""
    kernel = lambda p, name: p[name]["kernel"]

    def block(p, kind):
        out = {
            "ln1_g": p["ln_attn"]["scale"], "ln1_b": p["ln_attn"]["bias"],
            "ln2_g": p["ln_mlp"]["scale"], "ln2_b": p["ln_mlp"]["bias"],
            "w_gate": kernel(p, "gate"), "w_up": kernel(p, "up"), "w_down": kernel(p, "down"),
        }
        if kind in ("mamba", "mamba_memory"):
            return out | {
                "w_in": kernel(p, "in_proj"), "conv_w": p["conv_w"], "conv_b": p["conv_b"],
                "w_x": kernel(p, "x_proj"), "w_dt": kernel(p, "dt_proj"), "b_dt": p["dt_bias"],
                "A_log": p["A_log"], "D": p["D"], "w_out": kernel(p, "out_proj"),
            }
        if kind == "gmu":
            return out | {"w_in": kernel(p, "in_proj"), "w_out": kernel(p, "out_proj")}
        if kind == "cross":
            return out | {"wq": kernel(p, "q"), "wo": kernel(p, "proj")}
        return out | {"wqkv": kernel(p, "qkv"), "wo": kernel(p, "proj")}

    return {
        "wte": params["tok_embed"]["embedding"],
        "blocks": [block(params[f"block_{i}"], kind)
                   for i, kind in enumerate(config["layer_kinds"])],
        "lnf_g": params["ln_out"]["scale"], "lnf_b": params["ln_out"]["bias"],
    }


ADAM_B1 = 0.9  # optax.adam's default, which ``lm_trial._Trial`` takes
# the scan's own leaves, by the reference's names: where a wrong state,
# decay or step of the scan's backward would show first
SCAN_LEAVES = ("A_log", "D", "w_dt", "b_dt", "w_x", "conv_w", "conv_b")


def program_side(trial: _Trial, tokens):
    """``(logits, gradients, the parameters' change, the step's
    metrics)`` of the trial on the seed's initial weights, all on the
    host: ``SambaYLM.apply`` for the logits, and **the timed step**,
    the trial's own ``make_lm_train_step`` under its own Adam on a
    state made again from the trial's key, for the loss, the counter,
    the gradients (Adam's first moment after one step from zero is
    ``(1 - b1) g``) and what it added to the parameters."""
    model = trial.model
    trial.init_state()
    state, trial.state = trial.state, None
    logits = jax.jit(lambda p, t: model.apply({"params": p}, t)[0])(state.params, tokens)
    before = jax.device_get(state.params)
    after, metrics = trial.step(state, tokens)  # donates the state
    logits = jax.device_get(logits)
    grads = jax.tree.map(
        lambda mu: mu / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")),
    )
    moved = jax.tree.map(np.subtract, jax.device_get(after.params), before)
    return logits, grads, moved, jax.device_get(metrics)


def reference_check(cell, trial: _Trial, tokens) -> dict:
    """The program against the configuration's plain reference, on the
    seeded initial weights and ``tokens``, the traffic's own sequence:
    the executable compared is the one the window ran."""
    config, tol = cell.config, cell.config["compared"]
    tokens = trial.group.device_put(tokens, trial.group.batch_sharding)
    sys_logits, sys_grads, sys_moved, metrics = program_side(trial, tokens)
    trial.init_state()  # the step consumed the weights: the same key makes them again
    params, trial.state = trial.state.params, None

    ref = cell.reference()
    weights = reference_weights(params, config)
    hidden, ref_loss, ref_grads, ref_counters = jax.jit(
        lambda w, t: ref.hidden_loss_grads(w, t, config)
    )(weights, tokens)
    del params
    sys_grads, sys_moved = reference_weights(sys_grads, config), reference_weights(sys_moved, config)
    by_leaf = compare.tree_rel_l2(sys_grads, ref_grads)
    # The parameters' change against the reference's Adam step on the gradients the step
    # holds, which ``by_leaf`` has just held to the reference's: a step that leaves the
    # parameters as they were reads 1. On the reference's own gradients the step is the
    # rate times the gradient's sign, and every element smaller than its error turns a
    # whole step round: that reading repeats ``grad_rel_l2`` amplified, and is a note.
    adam = jax.jit(lambda g: ref.adam_first_step(g, cell.traffic["learning_rates"][0]))
    ref_moved = adam(ref_grads)
    del ref_grads
    toward_reference = compare.tree_rel_l2(sys_moved, ref_moved)
    del ref_moved
    own_moved = adam(sys_grads)
    del sys_grads
    moved = compare.tree_rel_l2(sys_moved, own_moved)
    del own_moved, sys_moved
    ref_logits = jax.jit(lambda x, w: ref.logits_of(x, w, config))(hidden, weights)
    del weights, hidden

    scan = {
        name: max(e for k, e in by_leaf.items() if k.endswith(f"['{name}']"))
        for name in SCAN_LEAVES
    }
    rms, ref_rms = np.asarray(metrics["ssm_state_rms"]), np.asarray(ref_counters["ssm_state_rms"])
    errors = {
        "logits_rel_rms": float(compare.rel_rms(sys_logits, ref_logits)),
        "loss_rel": abs(float(metrics["loss"]) - float(ref_loss)) / abs(float(ref_loss)),
        "grad_rel_l2": max(by_leaf.values()),
        "scan_grad_rel_l2": max(scan.values()),
        "param_change_rel_l2": max(moved.values()),
        "ssm_state_rms_rel": float(np.max(np.abs(rms - ref_rms) / ref_rms)),
    }
    ok, notes = compare.verdict(errors, tol)
    notes.append(
        f"loss program {float(metrics['loss']):.6f} reference {float(ref_loss):.6f}; "
        f"worst gradient leaves "
        f"{[(k, round(by_leaf[k], 4)) for k in sorted(by_leaf, key=by_leaf.get)[-3:][::-1]]}; "
        f"the scan's worst by name { {name: round(e, 4) for name, e in scan.items()} }; "
        f"{len(by_leaf)} of {len(by_leaf)} leaves judged"
    )
    worst = lambda errs: [(k, float(f"{errs[k]:.3g}")) for k in sorted(errs, key=errs.get)[-3:][::-1]]
    notes.append(
        f"the parameters' change against the reference's Adam step, worst leaves: on the step's "
        f"gradients {worst(moved)}; on the reference's (the rate times a sign: not held) "
        f"{worst(toward_reference)}"
    )
    notes.append(
        f"ssm_state_rms program {rms.round(6).tolist()} reference {ref_rms.round(6).tolist()}"
    )
    return {"ok": ok, "errors": errors, "notes": notes}


def run(cell, devices, seed: int, seconds: float, trace_dir, book) -> dict:
    """One run of one cell: ``moe_lm_trial.run``'s order and record,
    without the routing's counters."""
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
        "flops_per_unit": flops_phi4flash.train_flops_per_token(
            config, traffic["sequence_length"]
        ),
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
