"""Xing4.0-29B-A4B's decoder in plain ``jax.numpy``, float32.

The plain reference of the ``xing4.0-29b-a4b`` configuration: forward
pass, next-token loss and gradients, written from the model's public
``config.json`` (``xing4.0-29b-a4b.json`` beside this file has its keys)
and the two papers its residual path comes from (hyper-connections,
arXiv 2409.19606; manifold-constrained hyper-connections, arXiv
2512.24880), importing nothing of the program under test. No kernels,
no cache, no mixed precision, no sorting or grouping of tokens, no
layout tricks: every matrix product runs at
``default_matmul_precision("highest")``, attention builds its scores,
the expert layer is a loop over the experts held with a mask, the
residual state is one ``(B, T, n, d)`` array and Sinkhorn-Knopp is a
loop over ``(N, n, n)`` matrices.

A token's state is ``X`` in ``R^{n x d}``, ``n`` = ``hc_mult``. The
embedding is copied into the ``n`` streams; each layer has two
sublayers, latent attention and the FFN, each behind its own
hyper-connection; the streams are summed before the final norm. For a
sublayer ``F`` with its pre-norm inside, and the connection's
parameters ``norm (nd,)``, ``phi_pre``, ``phi_post`` ``(nd, n)``,
``phi_res (nd, n*n)`` (row-major), ``b_pre``, ``b_post`` ``(n,)``,
``b_res (n, n)``, scalar gates ``a_pre``, ``a_post``, ``a_res``::

    x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps) * norm          # over all nd entries
    H~pre = a_pre (x~ phi_pre) + b_pre       H~post = a_post (x~ phi_post) + b_post
    H~res = a_res mat(x~ phi_res) + b_res                             # (n, n)
    Hpre = sigmoid(H~pre)     Hpost = 2 sigmoid(H~post)
    M = exp(clamp(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps)
    u = sum_j Hpre[j] X[j]          y = F(u)          X'[i] = sum_j M[i, j] X[j] + Hpost[i] y

Inside the sublayers, with ``d`` = ``hidden_size`` and ``H`` heads::

    MLA:  y = rms(u);  c_q = rms(y W_qa);  q = c_q W_qb  as (H, nope + rope)
          [c_kv | k_r] = y W_kva;  c_kv = rms(c_kv);  [k_nope | v] per head = c_kv W_kvb
          q_rope and k_r rotated: pairs (2i, 2i+1), angle pos * inv_freq_i (YaRN, below)
          k = [k_nope | k_r, the same for every head]
          causal softmax(q k^T * mscale^2 / sqrt(nope + rope)) v, flattened, then W_o
    FFN of the first ``first_k_dense_replace`` layers: W_down(silu(y W_gate) * (y W_up)), y = rms(u)
    FFN of the rest: s = sigmoid(y W_r) over all ``router_width`` experts; the
          ``num_experts_per_tok`` largest of s + b are chosen (b: the selection
          bias, ``e_score_correction_bias``; one group); g = s at the chosen /
          (their sum + 1e-20) * ``routed_scaling_factor``;
          sum over the chosen experts e of g_e E_e(y)  +  E_shared(y),
          each expert W_down(silu(y W_gate) * (y W_up)).

**YaRN** (``rope_scaling``, type ``yarn``), for the pairs ``i`` of the
``rope``-wide rotary part, ``f_i = theta^(-2i/rope)``::

    turns(beta) = rope ln(original_max_position_embeddings / (2 pi beta)) / (2 ln theta)
    low = floor(turns(beta_fast)), high = ceil(turns(beta_slow)), kept inside [0, rope - 1]
    m_i = 1 - clip((i - low) / (high - low), 0, 1)
    inv_freq_i = (1 - m_i) f_i / factor + m_i f_i
    mscale(m) = 0.1 m ln(factor) + 1
    cos and sin times mscale(mscale) / mscale(mscale_all_dim)  (1 here); scores times mscale(mscale_all_dim)^2

**The chip's share.** ``experts_held = [first, count]``: of the routed
sum only the terms of experts ``first .. first + count - 1`` are added
(their weights are the only ones given); the router, the choice and the
normalisation are over all ``router_width`` experts. What the absent
experts would add is left out, as in the program. The vocabulary is
whatever ``wte`` and ``head`` hold.

Not here, as not in the program (``departures`` in the configuration's
file): the next-token-plus-one module and the rule that moves ``b``.

Four things are about fitting the chip machine at 4,096 tokens and
change no operation: attention runs one block of ``ATTENTION_BLOCK``
queries at a time against all the keys; each layer, and each such
block, is wrapped in ``jax.checkpoint``; the experts of a layer run
as one ``lax.scan`` over their stacked weights; and the Sinkhorn
iterations are the body of one ``lax.fori_loop`` (``sinkhorn`` says
why, and keeps the unrolled loop beside it).

Weights come in as a dict: ``wte (V, d)``, ``blocks``: a list of dicts
with ``ln1 w_qa q_norm w_qb w_kva kv_norm w_kvb wo ln2``, the two
connections ``hc_attn`` and ``hc_mlp`` (dicts of the ten parameters
above) and either ``w_gate w_up w_down`` (a dense layer) or ``router
(d, E) score_bias (E,) e_gate e_up (count, d, h) e_down (count, h, d)
s_gate s_up s_down`` (an expert layer); then ``lnf`` and ``head (d,
V)``. Matrices are stored ``(in, out)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION_BLOCK = 512  # queries a block; a T it does not divide runs whole


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def yarn_inv_freq(theta, width, scaling):
    """The frequency of each pair of a ``width``-wide rotary part,
    float64; ``scaling`` is the config's ``rope_scaling`` or ``None``."""
    freq = float(theta) ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    if scaling is None:
        return freq
    length = scaling["original_max_position_embeddings"]
    turns = lambda beta: width * math.log(length / (2 * math.pi * beta)) / (2 * math.log(theta))
    low = max(math.floor(turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns(scaling["beta_slow"])), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    m = 1.0 - ramp
    return (1.0 - m) * freq / scaling["factor"] + m * freq


def yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotate_pairs(x, theta, scaling):
    """``x``: ``(B, T, H, rope)``. Pair ``i`` is elements ``(2i, 2i+1)``."""
    t, width = x.shape[1], x.shape[-1]
    inv_freq = jnp.asarray(yarn_inv_freq(theta, width, scaling), jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # (T, rope/2)
    size = 1.0
    if scaling is not None:
        size = yarn_mscale(scaling["factor"], scaling["mscale"]) / yarn_mscale(
            scaling["factor"], scaling["mscale_all_dim"])
    cos, sin = size * jnp.cos(angle)[None, :, None, :], size * jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (width // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention(q, k, v, scale):
    """Causal softmax attention with scores ``q k^T * scale``; q, k
    ``(B, T, H, Dq)``, v ``(B, T, H, Dv)``."""
    b, t, h, dq = q.shape
    block = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block, H, Dv)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, v.shape[-1])


def mla(y, w, config):
    b, t, _ = y.shape
    h = config["num_attention_heads"]
    nope, rope, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    rank, eps, theta = config["kv_lora_rank"], config["rms_norm_eps"], config["rope_theta"]
    scaling = config.get("rope_scaling")
    scale = 1.0 / math.sqrt(nope + rope)
    if scaling is not None:
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    q = (rms(y @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(b, t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta, scaling)], axis=-1)
    latent = y @ w["w_kva"]
    k_r = rotate_pairs(latent[:, :, None, rank:], theta, scaling)  # (B, T, 1, rope)
    kv = (rms(latent[..., :rank], w["kv_norm"], eps) @ w["w_kvb"]).reshape(b, t, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope))], axis=-1)
    return attention(q, k, kv[..., nope:], scale).reshape(b, t, h * dv) @ w["wo"]


def swiglu(y, gate, up, down):
    return (silu(y @ gate) * (y @ up)) @ down


def route(y, w, config):
    """``(chosen (N, k) int32, weights (N, k))`` over all the router's experts."""
    scores = 1.0 / (1.0 + jnp.exp(-(y @ w["router"])))
    _, chosen = jax.lax.top_k(scores + w["score_bias"], config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * config["routed_scaling_factor"]


def experts(y, w, config):
    """``(output, chosen, assignments per expert held)`` for ``y`` ``(N, d)``."""
    first, count = config["experts_held"]
    chosen, weights = route(y, w, config)

    def add_expert(out, expert):
        e, gate, up, down = expert
        here = chosen == first + e  # (N, k); an expert is chosen at most once a token
        g = jnp.sum(jnp.where(here, weights, 0.0), axis=-1, keepdims=True)
        return out + g * swiglu(y, gate, up, down), jnp.sum(here)

    out, counts = jax.lax.scan(
        add_expert,
        swiglu(y, w["s_gate"], w["s_up"], w["s_down"]),
        (jnp.arange(count), w["e_gate"], w["e_up"], w["e_down"]),
    )
    return out, chosen, counts


def sinkhorn(logits, config, unrolled=False):
    """``(N, n, n)`` -> the same shape: ``exp`` of the clamped logits,
    then columns and rows normalised in turn, ``hc_sinkhorn_iters``
    times. ``unrolled``: the iterations as a Python loop, as one writes
    them down; otherwise the same two lines as the body of a
    ``lax.fori_loop``, which is what the model runs (unrolled, the ten
    connections' 200 iterations and their backward made the float32
    program 41 MB of code, and the cell's executables together passed
    the chip machine's 192 MiB compile cache). A test holds the two to
    each other, values and gradients."""
    m = jnp.exp(jnp.clip(logits, config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]))

    def normalise(m):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + config["hc_eps"])  # each column: over rows
        return m / (jnp.sum(m, axis=2, keepdims=True) + config["hc_eps"])  # each row: over columns

    if unrolled:
        for _ in range(config["hc_sinkhorn_iters"]):
            m = normalise(m)
        return m
    return jax.lax.fori_loop(0, config["hc_sinkhorn_iters"], lambda _, m: normalise(m), m)


def connection_maps(x, w, config):
    """``x`` ``(N, n, d)`` -> ``Hpre (N, n)``, ``Hpost (N, n)``, ``Hres (N, n, n)``."""
    tokens, n, d = x.shape
    flat = x.reshape(tokens, n * d)
    xt = flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                         + config["rms_norm_eps"]) * w["norm"]
    pre = 1.0 / (1.0 + jnp.exp(-(w["a_pre"] * (xt @ w["phi_pre"]) + w["b_pre"])))
    post = 2.0 / (1.0 + jnp.exp(-(w["a_post"] * (xt @ w["phi_post"]) + w["b_post"])))
    res = sinkhorn(w["a_res"] * (xt @ w["phi_res"]).reshape(tokens, n, n) + w["b_res"], config)
    return pre, post, res


def marginal_err(res):
    """Largest distance of a row or column sum of any of the ``(N, n,
    n)`` matrices from 1."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(res, axis=2) - 1.0)))


def connected(x, w, sublayer, config):
    """One sublayer behind its hyper-connection: ``x`` ``(B, T, n, d)``
    -> ``(x', what the sublayer returned beside y, marginal error)``."""
    b, t, n, d = x.shape
    pre, post, res = connection_maps(x.reshape(b * t, n, d), w, config)
    err = marginal_err(res)
    pre, post, res = pre.reshape(b, t, n), post.reshape(b, t, n), res.reshape(b, t, n, n)
    y, extra = sublayer(jnp.einsum("btj,btjd->btd", pre, x))
    mixed = jnp.einsum("btij,btjd->btid", res, x) + post[..., None] * y[:, :, None, :]
    return mixed, extra, err


def ffn(u, w, config):
    y = rms(u, w["ln2"], config["rms_norm_eps"])
    if "router" not in w:
        none = jnp.zeros((0,), jnp.int32)
        return swiglu(y, w["w_gate"], w["w_up"], w["w_down"]), (none, none)
    b, t, d = y.shape
    out, chosen, counts = experts(y.reshape(b * t, d), w, config)
    return out.reshape(b, t, d), (chosen, counts)


def block(x, w, config):
    eps = config["rms_norm_eps"]
    x, _, err_attn = connected(
        x, w["hc_attn"], lambda u: (mla(rms(u, w["ln1"], eps), w, config), None), config)
    x, picked, err_ffn = connected(x, w["hc_mlp"], lambda u: ffn(u, w, config), config)
    return x, picked, jnp.maximum(err_attn, err_ffn)


def forward(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, V) float32 logits, per expert layer the
    experts chosen (N, k) and the assignments to each expert held, and
    the largest marginal error of any layer's Hres)``."""
    x = weights["wte"][tokens]
    x = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (config["hc_mult"], x.shape[-1]))
    routing, errs = [], []
    for w in weights["blocks"]:
        x, picked, err = jax.checkpoint(lambda x, w: block(x, w, config))(x, w)
        errs.append(err)
        if "router" in w:
            routing.append(picked)
    x = jnp.sum(x, axis=2)
    logits = rms(x, weights["lnf"], config["rms_norm_eps"]) @ weights["head"]
    chosen, counts = zip(*routing)
    return logits, {
        "chosen": jnp.stack(chosen), "expert_counts": jnp.stack(counts),
        "hc_marginal_err": jnp.max(jnp.stack(errs)),
    }


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position ``i`` predicting token ``i+1``,
    over the ``T-1`` positions that have a next token and over the
    batch."""
    logits, targets = logits[:, :-1], tokens[:, 1:]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def logits_loss_grads(weights, tokens, config):
    """Everything the comparison needs, in one traced function:
    ``(logits, loss, gradients, routing)``."""
    with jax.default_matmul_precision("highest"):
        weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)

        def loss_of(w):
            logits, routing = forward(w, tokens, config)
            return next_token_loss(logits, tokens), (logits, routing)

        (loss, (logits, routing)), grads = jax.value_and_grad(loss_of, has_aux=True)(weights)
    return logits, loss, grads, routing
