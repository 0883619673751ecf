"""How a GPT-2-kind configuration is driven through the program.

As ``examples/lm_hpo.py`` drives its trials: ``setup_groups`` carves
one single-chip submesh per trial, each trial is a ``TransformerLM``
with a state from ``create_lm_state`` and a step from
``make_lm_train_step``, and one host loop dispatches one optimizer step
per trial per round, round-robin, with no barrier between trials. Every
step trains on a fresh batch that ``synthetic_corpus(seed).batch``
draws on the host, so a falling loss is part of ``correct``.

**A reading.** The loop keeps one round in flight: it draws and
dispatches round ``i+1`` for every trial, then waits for every trial's
loss of round ``i`` and stamps the clock. A reading is the interval
between two successive stamps: one whole optimizer step of every trial,
on the device's own pace (the device always has the next step queued).
The window opens at the stamp that ends the warm rounds and closes at
the first stamp ``seconds`` later that has 40 readings behind it
(``readings.window_open``).

:func:`run` is a plain function of the devices and of the cell's two
data files, so the CPU rehearsal (``benchmark/tests``) runs the same
control flow at a tiny size; only ``benchmark/run.py`` insists on the
chip.
"""

from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import compare, flops, readings, trace_reduce
from multidisttorch_tpu.data import synthetic_corpus
from multidisttorch_tpu.models.transformer import TransformerLM
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step
from multidisttorch_tpu.train.steps import TrainState

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
WARM_ROUNDS = 2  # the first compiles or loads the step, the second runs at pace
MIN_READINGS = 40  # a window goes on until it has them; past 2 x seconds it fails
CORPUS_TOKENS = 65536  # as examples/lm_hpo.py draws its corpus
REFERENCE_SEQUENCES = 2  # what the comparison with the reference runs on
TRACED_SECONDS = 3.0  # length of the traced part that follows the window


def build_model(config: dict) -> TransformerLM:
    return TransformerLM(
        vocab_size=config["vocab_size"],
        d_model=config["n_embd"],
        num_heads=config["n_head"],
        num_layers=config["n_layer"],
        max_len=config["n_positions"],
        dtype=DTYPES[config["assumed"]["compute_dtype"]],
        remat=config["assumed"]["remat"],
    )


def reference_weights(params, n_layer: int) -> dict:
    """The program's parameter tree under the reference's names. No
    array is copied or reshaped: flax stores a matrix ``(in, out)`` as
    the reference does. Gradients go through the same renaming."""

    def block(p):
        return {
            "ln1_g": p["ln_attn"]["scale"], "ln1_b": p["ln_attn"]["bias"],
            "wq": p["q"]["kernel"], "bq": p["q"]["bias"],
            "wk": p["k"]["kernel"], "bk": p["k"]["bias"],
            "wv": p["v"]["kernel"], "bv": p["v"]["bias"],
            "wo": p["proj"]["kernel"], "bo": p["proj"]["bias"],
            "ln2_g": p["ln_mlp"]["scale"], "ln2_b": p["ln_mlp"]["bias"],
            "w_up": p["up"]["kernel"], "b_up": p["up"]["bias"],
            "w_down": p["down"]["kernel"], "b_down": p["down"]["bias"],
        }

    return {
        "wte": params["tok_embed"]["embedding"],
        "wpe": params["pos_embed"]["embedding"],
        "blocks": [block(params[f"block_{i}"]) for i in range(n_layer)],
        "lnf_g": params["ln_out"]["scale"], "lnf_b": params["ln_out"]["bias"],
        "head_w": params["head"]["kernel"], "head_b": params["head"]["bias"],
    }


def reference_check(cell, group, model, params, tokens) -> dict:
    """The program against the configuration's plain reference, on the
    seeded initial weights and ``tokens``. The program's side is what a
    trial runs: ``TransformerLM.apply`` for the logits, and one step of
    ``make_lm_train_step`` under ``optax.sgd(1.0)``, whose parameter
    change is the gradient, for the loss and the gradients."""
    config, tol = cell.config, cell.config["compared"]
    tokens = group.device_put(tokens, group.batch_sharding)

    sys_logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
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
    ref_logits, ref_loss, ref_grads = jax.jit(
        lambda w, t: ref.logits_loss_grads(w, t, config)
    )(reference_weights(params, config["n_layer"]), tokens)

    by_leaf = compare.tree_rel_l2(
        reference_weights(sys_grads, config["n_layer"]), ref_grads
    )
    # A gradient that is zero by construction (the key bias: a constant
    # added to every score of a row leaves the softmax unchanged) is
    # rounding noise on both sides and has no relative error to speak of.
    norms = {k: float(v) for k, v in compare.tree_rms(ref_grads).items()}
    floor = 1e-3 * statistics.median(norms.values())
    judged = {k: e for k, e in by_leaf.items() if norms[k] > floor}
    worst = max(judged, key=judged.get)
    errors = {
        "logits_rel_rms": float(compare.rel_rms(sys_logits, ref_logits)),
        "loss_rel": abs(float(metrics["loss"]) - float(ref_loss)) / abs(float(ref_loss)),
        "grad_rel_l2": judged[worst],
    }
    ok, notes = compare.verdict(errors, tol)
    notes.append(
        f"loss program {float(metrics['loss']):.6f} reference {float(ref_loss):.6f}; "
        f"worst gradient leaf {worst}; {len(judged)} of {len(by_leaf)} leaves judged"
    )
    return {"ok": ok, "errors": errors, "notes": notes}


class _Trial:
    """One trial of the sweep: its submesh, state, step and batch draws."""

    def __init__(self, group, model, lr, index, seed, traffic, corpus):
        self.group, self.model = group, model
        self.tx = optax.adam(lr)
        self.shape = (traffic["batch_sequences"], traffic["sequence_length"])
        self.corpus = corpus
        self.draws = np.random.default_rng([seed, index])
        self.key = jax.random.key(seed * 1009 + index)
        self.step = make_lm_train_step(group, model, self.tx)
        self.state = None
        self.losses: list = []

    def init_state(self):
        self.state = create_lm_state(self.group, self.model, self.tx, self.key)

    def dispatch(self, input_s: list):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("host:_input"):
            rows = self.corpus.batch(self.draws, *self.shape)
            tokens = self.group.device_put(rows, self.group.batch_sharding)
        input_s.append(time.perf_counter() - t0)
        with jax.profiler.TraceAnnotation("host:_dispatch"):
            self.state, metrics = self.step(self.state, tokens)
        return metrics["loss"]


class _Loop:
    """The round-robin host loop with one round in flight."""

    def __init__(self, trials):
        self.trials = trials
        self.input_s: list[float] = []
        self.in_flight = None

    def dispatch_round(self):
        return [t.dispatch(self.input_s) for t in self.trials]

    def advance(self) -> float:
        """Dispatch the next round, wait for the one in flight, stamp."""
        nxt = self.dispatch_round()
        stamp = self.drain()
        self.in_flight = nxt
        return stamp

    def drain(self) -> float:
        """Wait for the round in flight and stamp; nothing is queued after."""
        with jax.profiler.TraceAnnotation("host:_wait"):
            for loss in self.in_flight:
                loss.block_until_ready()
        stamp = time.perf_counter()
        for t, loss in zip(self.trials, self.in_flight):
            t.losses.append(loss)
        self.in_flight = None
        return stamp


def _start_trace(trace_dir: str) -> None:
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the benchmark's own spans are TraceMes
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def run(cell, devices, seed: int, seconds: float, trace_dir, book) -> dict:
    """One run of one cell. Returns the run's record: spans, stamps,
    counters, the reduced trace (``trace_dir`` given) and what
    ``correct`` rests on. Metric readers take it from there."""
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
    # Read before anything but training has touched the chips: the peak
    # is the trial path's own (state, step program, one batch ahead).
    peak_bytes = _peak_bytes(devices[:n])

    trace = None
    if trace_dir is not None:
        # The traced part follows the window in the same steady loop, so
        # the profiler's own start and stop cost the readings nothing.
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
    # weights made again from the same key: it needs more memory than
    # training does, and before the window it would set the peak.
    t = time.perf_counter()
    for tr in trials:
        tr.state = None
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
        "units_per_reading_per_chip": traffic["batch_sequences"] * traffic["sequence_length"],
        "flops_per_unit": flops.lm_train_flops_per_token(
            config["n_embd"], config["n_layer"], traffic["sequence_length"], config["vocab_size"]
        ),
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


def _peak_bytes(devices) -> int:
    """The peak on the fullest chip, where the backend reports it."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
