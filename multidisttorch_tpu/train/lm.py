"""Language-model train/eval steps with sequence parallelism.

Next-token objective for :class:`models.transformer.TransformerLM`
under the same per-trial contract as the VAE/classifier steps. With
``sequence_parallel=True`` the token batch's TIME dimension is sharded
over the trial's data axis — the long-context regime where one
sequence exceeds a chip — and the model's ring attention exchanges K/V
blocks around the submesh ring while GSPMD reduces gradients over the
same axis. The full sequence length stays resident; only ``T/N`` of it
lives per chip.

Shift handling keeps shapes static and divisible (ring attention needs
``T % N == 0``): the model sees all ``T`` tokens, targets are the
input rolled left by one, and the final position is masked out of the
loss instead of slicing ``T-1``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from multidisttorch_tpu.ops.head_loss import lm_head_loss, lm_head_loss_weighted
from multidisttorch_tpu.parallel import mesh
from multidisttorch_tpu.parallel.mesh import DATA_AXIS, TrialMesh
from multidisttorch_tpu.train.steps import TrainState
from multidisttorch_tpu.utils.profiling import (
    SCOPE_LOOP_EXIT,
    SCOPE_LOSS,
    SCOPE_OPTIMIZER,
    SPAN_INIT_OPT,
    SPAN_INIT_PARAMS,
    SPAN_INIT_STATE,
    SPAN_PLACE_STATE,
    span,
)

# The name jax gives the train step's program, in its compile events and
# so in the compile log (``utils/compile_cache.py``): ``step_fn``'s own.
# :func:`make_lm_train_step` returns the ``jax.jit`` object itself
# (callers use ``.lower``), so the step's trace, lowering and load are
# read from the log by this name and not from a wrapper on the hot path.
STEP_PROGRAM = "step_fn"


def _logits(out):
    """Model outputs are logits, or (logits, aux) from the MoE LM."""
    return out[0] if isinstance(out, tuple) else out


def _filter_logits(logits, top_k, top_p):
    """Top-k / nucleus filtering for sampling, shared by both samplers.

    ``top_k``: keep the k highest logits per row. ``top_p``: keep the
    smallest set of tokens whose probability mass reaches p (the
    highest-probability token always survives). Both may combine.

    RANK-based, not value-threshold: one stable descending argsort
    (ties resolved in index order, so rank 0 is exactly ``argmax``),
    masks computed in sorted space, scattered back to vocab positions
    — exact counts even on tied or uniform logits, and one sort serves
    both filters.
    """
    b, v = logits.shape
    if top_k is not None and not 1 <= top_k <= v:
        raise ValueError(f"top_k={top_k} must be in [1, vocab={v}]")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} must be in (0, 1]")
    idx = jnp.argsort(-logits, axis=-1)  # descending, argmax-stable
    sorted_logits = jnp.take_along_axis(logits, idx, axis=-1)
    keep = jnp.ones((b, v), bool)
    if top_k is not None:
        keep &= jnp.arange(v)[None, :] < top_k
    if top_p is not None:
        cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # smallest prefix with mass >= p; the top token always stays
        keep &= jnp.concatenate(
            [jnp.ones((b, 1), bool), cum[:, :-1] < top_p], axis=-1
        )
    keep_vocab = (
        jnp.zeros((b, v), bool)
        .at[jnp.arange(b)[:, None], idx]
        .set(keep)
    )
    return jnp.where(keep_vocab, logits, jnp.float32(-jnp.inf))


def _validate_sampling(temperature, top_k, top_p, vocab_size=None) -> None:
    """Build-time validation shared by both sampler factories: bad
    values fail at construction, not on the first jitted call (and
    filters are never silently dropped by a greedy temperature).
    Factories know their model's vocab, so an out-of-range ``top_k``
    is also a construction error, not a first-call trace error."""
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k={top_k} must be >= 1")
    if top_k is not None and vocab_size is not None and top_k > vocab_size:
        raise ValueError(
            f"top_k={top_k} exceeds the model's vocab_size={vocab_size}"
        )
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} must be in (0, 1]")
    if temperature <= 0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p require temperature > 0 (greedy sampling "
            "ignores filters; refusing to drop them silently)"
        )


def _sample_token(logits, rng, temperature, top_k, top_p):
    """One draw shared by both samplers: greedy at temperature 0, else
    (optionally filtered) softmax-temperature sampling. Returns
    ``(token, new_rng)``."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1), rng
    rng, sub = jax.random.split(rng)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None or top_p is not None:  # static: no-op filters
        logits = _filter_logits(logits, top_k, top_p)  # cost nothing
    return jax.random.categorical(sub, logits, axis=-1), rng


def next_token_nll(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Each position's cross-entropy against the next token: logits
    ``(..., B, T, V)``, ``tokens`` ``(B, T)``, the result ``(..., B,
    T)`` float32. The last position's target wraps around the roll: the
    caller masks it."""
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    targets = jnp.broadcast_to(targets[..., None], (*logp.shape[:-1], 1))
    return -jnp.take_along_axis(logp, targets, axis=-1)[..., 0]


def lm_loss_mean(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; the last position is masked (its
    target would wrap around the roll).

    The definition of the LM objective: the eval step computes it, and
    so does the train step of a trial whose operands lie on more than
    one device or whose model offers only logits. A one-device trial of
    the LMs here trains on ``ops/head_loss.py::lm_head_loss``, the same
    loss and gradients made without the ``(B, T, V)`` logits, which
    ``tests/test_head_loss.py`` holds to this function differentiated
    through a float32 head."""
    nll = next_token_nll(logits, tokens)
    t = tokens.shape[1]
    w = (jnp.arange(t) < t - 1).astype(jnp.float32)[None, :]
    return jnp.sum(nll * w) / jnp.sum(w) / tokens.shape[0]


def _lm_shardings(trial: TrialMesh, sequence_parallel: bool, shardings):
    """The one copy of the LM input/state sharding contract shared by
    the train, eval, and scan-fused step builders: ``(B, T)`` tokens
    shard T over the data axis under sequence parallelism (batch
    replicated), else B (plain DP). ``(K, B, T)`` stacked chunks are
    the same contract with a leading unsharded scan axis — derived
    here from the tokens spec so the two can never drift."""
    repl = trial.replicated_sharding
    tokens_sh = (
        trial.sharding(None, DATA_AXIS)
        if sequence_parallel
        else trial.batch_sharding
    )
    spec = tuple(tokens_sh.spec) + (None,) * (2 - len(tokens_sh.spec))
    chunks_sh = trial.sharding(None, *spec)
    return repl, tokens_sh, chunks_sh, (repl if shardings is None else shardings)


def lm_chunk_sharding(trial: TrialMesh, *, sequence_parallel: bool = False):
    """Placement helper for ``make_lm_multi_step`` inputs: the
    ``(K, B, T)`` stacked-chunk ``NamedSharding`` (leading scan axis
    unsharded; B or T over the data axis per the tokens contract).
    Callers should ``device_put`` chunks with THIS rather than
    restating the spec — it is derived from the same ``_lm_shardings``
    source as the step builders, so placement can't drift from what
    the jitted program expects (which would trigger a resharding copy
    on every dispatch)."""
    return _lm_shardings(trial, sequence_parallel, None)[2]


def make_lm_train_step(
    trial: TrialMesh,
    model: Any,
    tx: optax.GradientTransformation,
    *,
    sequence_parallel: bool = False,
    shardings: Any = None,
    aux_loss_weight: float = 1e-2,
) -> Callable[[TrainState, jax.Array], tuple[TrainState, dict]]:
    """``step(state, tokens) -> (state, {loss})`` — ``tokens`` is
    ``(B, T) int32``; with ``sequence_parallel`` the T dimension is
    sharded over the data axis (batch replicated), otherwise B is
    sharded (plain DP). For activation rematerialization construct the
    model with ``TransformerLM(remat=True)`` — per-BLOCK checkpointing,
    the placement that actually cuts peak HBM (a whole-forward
    ``jax.checkpoint`` here would recompute everything and save
    nothing); a block's input is saved, and of what the block made the
    few values that cost most to remake a byte, up to 14 KB a token
    and layer where a block keeps its attention's operands and its
    MLP's pre-activation (``models/decoder.py::remat_block`` lists
    them). A model returning
    ``(logits, aux)`` with a scalar ``aux`` (the MoE LM's Switch
    load-balancing term) trains on
    ``lm_loss + aux_loss_weight * aux``; one returning ``(logits,
    {name: counter})`` (``LatentMoELM``'s assignments per expert held)
    trains on the loss alone and the counters come out beside it in
    the step's metrics, read with the loss.

    Head and loss take one of two paths, chosen while tracing from what
    the step can see (:func:`_build_lm_step_fn`): a model that hands
    back its state after the last norm and names its head's weights
    (``__call__(tokens, head=False)`` and ``head_weights(params)``:
    every LM of ``models/``), on operands that lie on one device, is
    asked for that state and ``ops/head_loss.py::lm_head_loss`` walks
    it in blocks of positions, loss and gradients at once, the float32
    logits never whole; any other model (a user's module, a pipeline
    stage), and any trial over several devices (a walk over blocks of
    rows of a batch- or sequence-sharded array would make GSPMD gather
    it), is asked for ``(B, T, V)`` logits and :func:`lm_loss_mean` is
    differentiated through them. A model whose blocks run several times
    a token (one that offers ``exit_log_probs``, ``models/looped.py``)
    trains on the loss of every pass weighted by its exit probability
    (:func:`_looped_loss`), on either path, and its counters ``exit_p``
    and ``loop_loss`` come out beside the loss."""
    repl, tokens_sh, _, state_sh = _lm_shardings(
        trial, sequence_parallel, shardings
    )
    step_fn = _build_lm_step_fn(model, tx, aux_loss_weight)
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, tokens_sh),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,),
    )


def _build_lm_step_fn(model, tx, aux_loss_weight):
    """The un-jitted LM optimizer step shared by the single-dispatch and
    scan-fused factories (one copy of the loss/update math, so the two
    cannot drift; :func:`make_lm_train_step` says which of the head's
    and loss's two paths a trial takes)."""

    def step_fn(state: TrainState, tokens: jax.Array):
        placed = mesh.placement(tokens)  # None: no mesh to see, so one device
        walk = hasattr(model, "head_weights") and (placed is None or placed[1] == 1)

        def loss_fn(params):
            if hasattr(model, "exit_log_probs"):
                return _looped_loss(model, params, tokens, walk)
            if walk:
                out = model.apply({"params": params}, tokens, head=False)
                weights, bias, tied = model.head_weights(params)
                loss = lm_head_loss(_logits(out), weights, bias, tokens, model.dtype, tied)
            else:
                out = model.apply({"params": params}, tokens)
                with jax.named_scope(SCOPE_LOSS):
                    loss = lm_loss_mean(_logits(out), tokens)
            counters = {}
            if isinstance(out, tuple) and isinstance(out[1], dict):
                counters = out[1]  # counted, not trained on
            elif isinstance(out, tuple):
                loss = loss + aux_loss_weight * out[1]
            return loss, counters

        (loss, counters), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                params=new_params, opt_state=new_opt, step=state.step + 1
            ),
            {"loss": loss.astype(jnp.float32), **counters},
        )

    return step_fn


def _looped_loss(model, params, tokens, walk):
    """``(loss, counters)`` of a model whose blocks run several times a
    token (``models/looped.py``), which hands back ``(U, B, T, d)`` states,
    or ``(U, B, T, V)`` logits, and its exit gates' ``(U, B, T)`` logits:
    the expected next-token loss over the exit distribution minus
    ``model.exit_entropy_weight`` times the distribution's entropy, the
    mean over the positions that have a next token of ``sum_t p_t CE_t +
    beta sum_t p_t log p_t``. On the walk the U states are stacked along
    the batch and walked once, each position weighted by its loop's
    ``p``: one accumulated gradient of the head's weights. Counters: ``exit_p`` and
    ``loop_loss``, the mean ``p_t`` and the mean cross-entropy of each
    loop, ``(U,)``."""
    out, gate_logits = model.apply({"params": params}, tokens, head=not walk)
    loops, b, t = gate_logits.shape
    positions = b * (t - 1)
    has_next = (jnp.arange(t) < t - 1).astype(jnp.float32)
    with jax.named_scope(SCOPE_LOOP_EXIT):
        log_p = model.exit_log_probs(gate_logits)
        p = jnp.exp(log_p)
    if walk:
        weights, bias, tied = model.head_weights(params)
        # the walk divides by all U x B x (T - 1) positions it is given, so
        # its weights are U p: the loss's cotangent stays 1 and nothing of
        # the walk's size is multiplied again in the backward pass
        expected, ce = lm_head_loss_weighted(
            out.reshape(loops * b, t, -1), weights, bias, jnp.tile(tokens, (loops, 1)),
            model.dtype, tied, (loops * p).reshape(loops * b, t),
        )
        ce = ce.reshape(loops, b, t)
    else:
        with jax.named_scope(SCOPE_LOSS):
            ce = next_token_nll(out, tokens) * has_next
            expected = jnp.sum(p * ce) / positions
    with jax.named_scope(SCOPE_LOOP_EXIT):
        negentropy = jnp.sum(p * log_p * has_next) / positions
        loss = expected + model.exit_entropy_weight * negentropy
        counters = {
            "exit_p": jnp.sum(p * has_next, axis=(1, 2)) / positions,
            "loop_loss": jnp.sum(ce, axis=(1, 2)) / positions,
        }
    return loss, counters


def make_lm_multi_step(
    trial: TrialMesh,
    model: Any,
    tx: optax.GradientTransformation,
    *,
    sequence_parallel: bool = False,
    shardings: Any = None,
    aux_loss_weight: float = 1e-2,
) -> Callable[[TrainState, jax.Array], tuple[TrainState, dict]]:
    """K chained LM optimizer steps in ONE dispatch, via ``lax.scan``.

    The LM analog of :func:`train.steps.make_multi_step`, and for the
    same reason (docs/DISPATCH.md): a single LM step at bench scale is
    ~1 ms of device time on a v5e, the same order as one host enqueue,
    so a step-per-dispatch loop leaves the chip idle half the time.
    ``token_chunks`` is ``(K, B, T) int32`` — sharded over the submesh
    data axis on B (plain DP) or T (``sequence_parallel``) — and
    ``metrics['loss']`` comes back ``(K,)``, the same per-step logging
    contract as the single-step factory. Per-step activations do not
    accumulate across the scan (each iteration differentiates and
    updates inside its own body).
    """
    repl, _, chunks_sh, state_sh = _lm_shardings(
        trial, sequence_parallel, shardings
    )
    step_fn = _build_lm_step_fn(model, tx, aux_loss_weight)

    def multi_fn(state: TrainState, token_chunks: jax.Array):
        def body(s, toks):
            s, metrics = step_fn(s, toks)
            return s, metrics["loss"]

        state, losses = jax.lax.scan(body, state, token_chunks)
        return state, {"loss": losses}

    return jax.jit(
        multi_fn,
        in_shardings=(state_sh, chunks_sh),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,),
    )


def make_lm_eval_step(
    trial: TrialMesh,
    model: Any,
    *,
    sequence_parallel: bool = False,
    shardings: Any = None,
) -> Callable[[TrainState, jax.Array], dict]:
    """``eval(state, tokens) -> {loss, perplexity}`` — same next-token
    objective and token sharding contract as :func:`make_lm_train_step`,
    no gradient."""
    repl, tokens_sh, _, state_sh = _lm_shardings(
        trial, sequence_parallel, shardings
    )

    def eval_fn(state: TrainState, tokens: jax.Array):
        out = model.apply({"params": state.params}, tokens)
        loss = lm_loss_mean(_logits(out), tokens)
        return {
            "loss": loss.astype(jnp.float32),
            "perplexity": jnp.exp(loss).astype(jnp.float32),
        }

    return jax.jit(
        eval_fn, in_shardings=(state_sh, tokens_sh), out_shardings=repl
    )


def create_lm_state(
    trial: TrialMesh,
    model: Any,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    example_len: Optional[int] = None,
    param_shardings: Any = None,
) -> TrainState:
    """Initialize and place an LM state on the trial submesh.

    ``example_len`` shapes the init dummy; for ring-attention models the
    sequence length must divide the trial's data-axis extent, so the
    default is ``8 * trial.data_size`` (always divisible; irrelevant to
    the resulting param shapes). ``param_shardings`` shards weights
    (e.g. ``parallel.fsdp.fsdp_param_shardings``) via the shared
    ``train.steps.place_sharded_state`` recipe — same contract as the
    VAE and classifier state creators.

    Timed where it happens, into the process's compile log
    (``utils/profiling.span``): ``admit:init_state`` around the whole,
    and inside it ``admit:init_params`` (``model.init``, eager: one
    small program an initializer and shape, each traced, lowered and
    loaded or compiled by itself), ``admit:init_opt`` (``tx.init``) and
    ``admit:place_state``. The spans are host time: dispatch and
    transfers are asynchronous, so the last programs and copies may
    still be running when ``admit:place_state`` closes.
    """
    from multidisttorch_tpu.train.steps import place_sharded_state

    if example_len is None:
        example_len = 8 * trial.data_size
    with span(SPAN_INIT_STATE):
        with span(SPAN_INIT_PARAMS):
            params = model.init(
                {"params": rng}, jnp.zeros((1, example_len), jnp.int32)
            )["params"]
        if param_shardings is not None:
            with span(SPAN_PLACE_STATE):  # tx.init runs on the placed weights
                return place_sharded_state(trial, params, tx, param_shardings)
        with span(SPAN_INIT_OPT):
            opt_state = tx.init(params)
        with span(SPAN_PLACE_STATE):
            return trial.device_put(
                TrainState(
                    params=params,
                    opt_state=opt_state,
                    step=jnp.zeros((), jnp.int32),
                )
            )


def make_lm_sample(
    trial: TrialMesh,
    model: Any,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    shardings: Any = None,
) -> Callable[[TrainState, jax.Array, int, jax.Array], jax.Array]:
    """Autoregressive sampling — the LM analog of the reference's
    prior-sample dump (vae-hpo.py:163-170: draw from the model, look at
    what it learned).

    ``sample(state, tokens, prompt_len, rng) -> (B, T) int32``: the
    ``(B, T)`` buffer holds the prompt in its first ``prompt_len``
    positions (the rest is ignored); positions ``prompt_len..T-1`` are
    filled autoregressively. Greedy at ``temperature=0``, else
    softmax-temperature sampling. Shapes stay static (one ``(B, T)``
    buffer; ``lax.fori_loop`` + ``dynamic_update_slice``) so one
    compilation serves every prompt length; each step recomputes the
    full prefix — O(T^2) attention per token, the simple exact
    formulation (a KV cache is a bandwidth optimization, not a
    semantics change). Causal attention guarantees the padding beyond
    the current position cannot influence the next token.

    ``prompt_len`` is clamped to >= 1: position 0 is always taken from
    the buffer (a BOS/seed token) — "unconditional" sampling is
    sampling conditioned on a chosen first token, never on buffer
    garbage. The buffer batch-shards over the trial's data axis like
    every other LM step (B must divide it).
    """
    _validate_sampling(
        temperature, top_k, top_p, getattr(model, "vocab_size", None)
    )
    repl = trial.replicated_sharding

    def sample_fn(
        state: TrainState, tokens: jax.Array, prompt_len, rng: jax.Array
    ):
        def body(i, carry):
            buf, rng = carry
            out = model.apply({"params": state.params}, buf)
            nxt, rng = _sample_token(
                _logits(out)[:, i - 1], rng, temperature, top_k, top_p
            )
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, nxt[:, None].astype(buf.dtype), i, axis=1
            )
            return buf, rng

        start = jnp.maximum(prompt_len, 1)  # never index position -1
        buf, _ = jax.lax.fori_loop(
            start, tokens.shape[1], body, (tokens, rng)
        )
        return buf

    return jax.jit(
        sample_fn,
        in_shardings=(
            repl if shardings is None else shardings,
            trial.batch_sharding,
            None,
            repl,
        ),
        out_shardings=trial.batch_sharding,
    )
