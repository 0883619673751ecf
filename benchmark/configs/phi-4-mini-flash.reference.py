"""Phi-4-mini-flash-reasoning's decoder in plain ``jax.numpy``, float32.

The plain reference of the ``phi-4-mini-flash`` configuration: forward
pass, next-token loss and gradients, written from the model's public
``config.json`` (``phi-4-mini-flash.json`` beside this file has its
keys) and the SambaY paper (arXiv 2507.06607), importing nothing of the
program under test. No kernels, no chunked scan, no grouping of heads:
every matrix product runs at ``default_matmul_precision("highest")``,
the selective scan is a ``lax.scan`` over single time steps on the
``(E, N)`` state, the convolution a sum of four shifted copies, the KV
heads are repeated to one a query head, and a window layer is a full
score matrix under a mask built from positions.

Every layer is ``x += mixer(ln(x)); x += W_down(silu(W_gate h) * (W_up
h)), h = ln'(x)`` with a LayerNorm of scale and bias. The mixer by the
layer's kind (``config["layer_kinds"]``), ``E = expand * d``::

    mamba, mamba_memory:
        x, z = split(u W_in);  x = silu(conv_b + sum_j conv_w[j] * x[t - 3 + j])
        dt, B, C = split(x W_x);  D_t = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        h_t = exp(D_t A) * h_{t-1} + (D_t x_t) B_t^T;  y_t = h_t C_t + D x_t
        out = (y * silu(z)) W_out            # mamba_memory hands on y, the memory
    window, full_kv:
        q, k, v = split(u W_qkv) as (H, 64), (Hkv, 64), (Hkv, 64), k and v repeated H / Hkv times
        s = q k^T / 8 kept where key <= query and (window) query - key < sliding_window
        out = softmax(s) v W_o               # no positions; full_kv hands on k and v
    gmu:    out = (memory * silu(u W_in)) W_out
    cross:  q = u W_q over the full_kv layer's k and v, causal, no window; out = softmax(s) v W_o

then the final LayerNorm and the head, the embedding's transpose. The
released checkpoint's attention is differential attention, for which
the configuration has no key; plain softmax attention is run, here and
in the program (the file's ``departures``).

Three things are about fitting the chip machine at 16,384 tokens and
change no operation: the scan is walked ``SCAN_BLOCK`` steps at a time
under ``jax.checkpoint`` (the ``(T, E, N)`` states of a layer are 5.4
GB), attention runs ``ATTENTION_BLOCK`` queries at a time against all
the keys, and under the gradient the head and the loss run
``LOSS_BLOCK`` positions at a time; each layer and each such block is
wrapped in ``jax.checkpoint``, and the logits handed back are made
once, outside the gradient, from the same last hidden state
(``logits_of``).

Weights come in as a dict: ``wte (V, d)``; ``blocks``: a list of dicts
with ``ln1_g ln1_b ln2_g ln2_b w_gate w_up (d, mlp) w_down (mlp, d)``
and by kind ``w_in (d, 2E) conv_w (4, E) conv_b w_x (E, R + 2N) w_dt
(R, E) b_dt A_log (E, N) D w_out (E, d)``, ``wqkv wo``, ``w_in (d, E)
w_out``, ``wq wo``; then ``lnf_g lnf_b``. Matrices are stored ``(in,
out)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 256  # time steps a checkpointed block of the scan; a T it does not divide runs whole
ATTENTION_BLOCK = 256  # queries a block; likewise
LOSS_BLOCK = 2048  # positions a block of the head and the loss; likewise


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def causal_conv(x, w, b):
    """``y[t] = b + sum_j w[j] x[t - (taps - 1) + j]``, ``x (B, T, E)``."""
    t, taps = x.shape[1], w.shape[0]
    out = b
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate([jnp.zeros_like(x[:, :back]), x[:, : t - back]], axis=1)
        out = out + w[j] * shifted
    return out


def selective_scan(x, d, a, b, c, skip):
    """``(y (B, T, E), the last state (B, E, N))``; ``d`` the steps
    after softplus."""
    bsz, t, e = x.shape
    block = SCAN_BLOCK if t % SCAN_BLOCK == 0 else t

    def step(h, at):
        x_t, d_t, b_t, c_t = at  # (B, E), (B, E), (B, N), (B, N)
        h = jnp.exp(d_t[:, :, None] * a) * h + (d_t * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + skip * x_t

    @jax.checkpoint
    def one_block(h, operands):
        return jax.lax.scan(step, h, operands)

    in_blocks = lambda z: z.transpose(1, 0, 2).reshape(t // block, block, bsz, z.shape[-1])
    h, y = jax.lax.scan(
        one_block, jnp.zeros((bsz, e, a.shape[1]), x.dtype), tuple(in_blocks(z) for z in (x, d, b, c))
    )
    return y.reshape(t, bsz, e).transpose(1, 0, 2), h


def mamba(u, w, config):
    """``(out, y before the gate, rms of the last state)``."""
    e = w["A_log"].shape[0]
    n = config["assumed"]["d_state"]
    r = w["w_dt"].shape[0]
    xz = u @ w["w_in"]
    x, z = xz[..., :e], xz[..., e:]
    x = silu(causal_conv(x, w["conv_w"], w["conv_b"]))
    dbc = x @ w["w_x"]
    d = softplus(dbc[..., :r] @ w["w_dt"] + w["b_dt"])
    y, last = selective_scan(x, d, -jnp.exp(w["A_log"]), dbc[..., r:r + n], dbc[..., r + n:], w["D"])
    return (y * silu(z)) @ w["w_out"], y, jnp.sqrt(jnp.mean(jnp.square(last)))


def attention(q, k, v, window):
    """Causal softmax attention, ``window`` keys wide where given; q, k,
    v ``(B, T, H, D)``."""
    b, t, h, d = q.shape
    block = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # query - key
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d)


def self_attention(u, w, config, windowed):
    """``(out, (k, v) as (B, T, Hkv, D))``."""
    b, t, _ = u.shape
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // h
    qkv = u @ w["wqkv"]
    q = qkv[..., : h * hd].reshape(b, t, h, hd)
    k = qkv[..., h * hd: (h + hkv) * hd].reshape(b, t, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, t, hkv, hd)
    return attend(q, k, v, w, config["sliding_window"] if windowed else None), (k, v)


def attend(q, k, v, w, window):
    b, t, h, hd = q.shape
    repeat = lambda a: jnp.repeat(a, h // a.shape[2], axis=2)
    return attention(q, repeat(k), repeat(v), window).reshape(b, t, h * hd) @ w["wo"]


def cross_attention(u, w, kv, config):
    b, t, _ = u.shape
    h = config["num_attention_heads"]
    q = (u @ w["wq"]).reshape(b, t, h, config["hidden_size"] // h)
    return attend(q, *kv, w, None)


def block(x, w, kind, memory, kv, config):
    """``(x, what the layer hands on, rms of a Mamba layer's last
    state or None)``."""
    eps = config["layer_norm_eps"]
    u = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    handed = rms = None
    if kind in ("mamba", "mamba_memory"):
        out, y, rms = mamba(u, w, config)
        handed = y if kind == "mamba_memory" else None
    elif kind in ("window", "full_kv"):
        out, made = self_attention(u, w, config, kind == "window")
        handed = made if kind == "full_kv" else None
    elif kind == "gmu":
        out = (memory * silu(u @ w["w_in"])) @ w["w_out"]
    elif kind == "cross":
        out = cross_attention(u, w, kv, config)
    else:
        raise ValueError(kind)
    x = x + out
    h = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    return x + (silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"], handed, rms


def hidden(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, d) the last layer's output,
    {"ssm_state_rms": (Mamba layers,)})``."""
    x = weights["wte"][tokens]
    memory = kv = None
    rms = []
    for kind, w in zip(config["layer_kinds"], weights["blocks"], strict=True):
        x, handed, r = jax.checkpoint(
            lambda x, w, memory, kv, kind=kind: block(x, w, kind, memory, kv, config)
        )(x, w, memory if kind == "gmu" else None, kv if kind == "cross" else None)
        if kind == "mamba_memory":
            memory = handed
        elif kind == "full_kv":
            kv = handed
        if r is not None:
            rms.append(r)
    return x, {"ssm_state_rms": jnp.stack(rms)}


def head(x, weights, config):
    x = layer_norm(x, weights["lnf_g"], weights["lnf_b"], config["layer_norm_eps"])
    return x @ weights["wte"].T  # tie_word_embeddings


def forward(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, V) float32 logits, the counters)``."""
    x, counters = hidden(weights, tokens, config)
    return head(x, weights, config), counters


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position ``i`` predicting token ``i+1``,
    over the ``T-1`` positions that have a next token and over the
    batch."""
    logits, targets = logits[:, :-1], tokens[:, 1:]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def next_token_loss_by_blocks(x, weights, tokens, config):
    """:func:`next_token_loss` of ``head(x)``, the head and the
    log-softmax made ``LOSS_BLOCK`` positions at a time."""
    b, t, _ = x.shape
    block_ = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t
    targets = jnp.roll(tokens, -1, axis=1)  # the last position has no next token

    @jax.checkpoint
    def one_block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block_, axis=1)
        logits = head(cut(x), weights, config)
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        picked = jnp.take_along_axis(logp, cut(targets)[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(start + jnp.arange(block_) < t - 1, picked, 0.0))

    return jnp.sum(jax.lax.map(one_block, jnp.arange(0, t, block_))) / (b * (t - 1))


def hidden_loss_grads(weights, tokens, config):
    """``(last hidden state, loss, gradients, counters)``: everything
    the comparison needs but the logits, which :func:`logits_of` makes
    from the hidden state."""
    with jax.default_matmul_precision("highest"):
        weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)

        def loss_of(w):
            x, counters = hidden(w, tokens, config)
            return next_token_loss_by_blocks(x, w, tokens, config), (x, counters)

        (loss, (x, counters)), grads = jax.value_and_grad(loss_of, has_aux=True)(weights)
    return x, loss, grads, counters


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # ``assumed.optimizer``


def adam_first_step(grads, learning_rate):
    """What Adam's first step, from moments of zero, adds to each
    parameter: the moments of one gradient, each corrected for its
    start, ``- lr m / (sqrt(v) + eps)``."""

    def change(g):
        m, v = (1 - ADAM_B1) * g, (1 - ADAM_B2) * g * g
        m, v = m / (1 - ADAM_B1), v / (1 - ADAM_B2)
        return -learning_rate * m / (jnp.sqrt(v) + ADAM_EPS)

    return jax.tree.map(change, grads)


def logits_of(x, weights, config):
    with jax.default_matmul_precision("highest"):
        return head(x, jax.tree.map(lambda a: a.astype(jnp.float32), weights), config)


def logits_loss_grads(weights, tokens, config):
    """``(logits, loss, gradients, counters)`` in one traced function."""
    x, loss, grads, counters = hidden_loss_grads(weights, tokens, config)
    return logits_of(x, weights, config), loss, grads, counters
