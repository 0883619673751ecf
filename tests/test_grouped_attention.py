"""The grouped attention kernels of ``ops/pallas_attention.py`` (grouped
KV heads, an optional window, K and V by the block), interpreted,
against the masked dense form: forward and gradients, float32 at
``default_matmul_precision("highest")``, seeded. Nothing is timed."""

import math

import jax
import jax.numpy as jnp
import pytest

from multidisttorch_tpu.models.decoder import rope_angles, rope_halves
from multidisttorch_tpu.ops.pallas_attention import (
    blocked_window_attention,
    grouped_attention,
    grouped_takes_kernel,
)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _dense(q, k, v, window):
    """The masked dense form: KV heads repeated, the whole score matrix."""
    t, d, g = q.shape[1], q.shape[-1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)


def _operands(t, h, hkv, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shape = lambda heads: (2, t, heads, 128)
    return (jax.random.normal(keys[0], shape(h)), jax.random.normal(keys[1], shape(hkv)),
            jax.random.normal(keys[2], shape(hkv)), jax.random.normal(keys[3], shape(h)))


# window: none, shorter than a block, several blocks and a part, >= T (plain causal)
@pytest.mark.parametrize("window", [None, 50, 300, 512], ids=lambda w: f"w{w}")
@pytest.mark.parametrize("group", [1, 2, 4], ids=lambda g: f"g{g}")
def test_grouped_kernels_match_the_masked_dense_form(group, window):
    """Forward and the gradients of q, k and v, T = 512 as four K/V
    blocks of 128, q rotated in the kernels where a window is set (as
    the model's window layers are)."""
    q, k, v, co = _operands(512, 2 * group, 2)
    angle = rope_angles(jnp.arange(512), 10000.0, 128)
    rotation = (jnp.cos(angle), jnp.sin(angle)) if window else None

    def kernel(q, k, v):
        out = grouped_attention(q, k, v, window=window, q_rotation=rotation, block=128)
        return jnp.sum(out * co), out

    def dense(q, k, v):
        out = _dense(rope_halves(q, *rotation) if rotation else q, k, v, window)
        return jnp.sum(out * co), out

    with jax.default_matmul_precision("highest"):
        (_, got), got_grads = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(q, k, v)
        (_, want), want_grads = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(q, k, v)
    assert _rel(got, want) < 1e-5
    for have, need in zip(got_grads, want_grads, strict=True):
        assert _rel(have, need) < 1e-5


@pytest.fixture
def sub_of(monkeypatch):
    """Set the 128-wide pair's sub-step to a share of the block edge,
    with jax's caches emptied around it (the kernels' calls are jitted
    on the shapes alone)."""
    from multidisttorch_tpu.ops import pallas_attention

    def use(share):
        monkeypatch.setattr(pallas_attention, "_grouped_sub", lambda blk: blk // share)

    jax.clear_caches()
    yield use
    jax.clear_caches()


# (window, query heads a KV head, q rotated in the kernels, sub-step as a share of
# the block edge of 512): a full layer, a window of whole blocks (its far edge
# keeps key > query), a window of blocks and a part and one shorter than a block
# (their far edges run whole under the mask); 1 and 7 heads a KV head; both
# candidate sub-steps, half and a quarter of the block.
@pytest.mark.parametrize("window, group, rotated, share", [
    (None, 1, True, 2), (None, 7, False, 4), (None, 7, True, 2),
    (1024, 7, True, 2), (1024, 7, True, 4), (1024, 1, False, 4), (1024, 1, True, 2),
    (700, 7, True, 4), (700, 1, False, 2), (300, 7, False, 2),
], ids=lambda x: str(x))
def test_sub_stepped_masked_tiles_match_the_plain_path(sub_of, window, group, rotated, share):
    """The 128-wide pair with its diagonal and far-edge tiles walked in
    sub-steps of queries, T = 1,536 as three blocks of 512: the output,
    the logsumexp and the gradients of q, k and v against
    ``blocked_window_attention`` and the masked dense scores."""
    from multidisttorch_tpu.ops import pallas_attention

    sub_of(share)
    t, blk, hkv = 1536, 512, 2 if group == 1 else 1
    q, k, v, co = (x[:1] for x in _operands(t, group * hkv, hkv, seed=group + share))
    angle = rope_angles(jnp.arange(t), 1e6, 128)
    rotation = (jnp.cos(angle), jnp.sin(angle)) if rotated else None
    q_in = rope_halves(q, *rotation) if rotated else q
    loss = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * co), (0, 1, 2))
    kernel = lambda q, k, v: grouped_attention(
        q, k, v, window=window, q_rotation=rotation, block=blk)
    plain = lambda q, k, v: blocked_window_attention(
        rope_halves(q, *rotation) if rotated else q, k, v, window=window, block=256)
    with jax.default_matmul_precision("highest"):
        got = kernel(q, k, v), loss(kernel)(q, k, v)
        want = plain(q, k, v), loss(plain)(q, k, v)
        tables = None if rotation is None else pallas_attention._halves_tables(*rotation)
        flat = lambda x: x.reshape(1, t, -1)
        _, lse = pallas_attention._grouped_fwd_call(
            flat(q), flat(k), flat(v), tables, 1 / math.sqrt(128), window, blk, True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_in, jnp.repeat(k, group, axis=2)) / math.sqrt(128)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    want_lse = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1).reshape(1, hkv, group, t)
    assert _rel(lse, want_lse) < 1e-6
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert _rel(have, need) < 1e-5


def test_masked_tiles_cost_only_what_their_sub_steps_keep(sub_of):
    """The (query, key) products a query head's visits compute, in whole
    tiles, at the cells' shapes (block 1,024): ``moe-swa-t16384``'s
    window layer (T 16,384, window 4,096: 16 diagonals and 12 far edges
    of 70 tiles), its full layer (16 diagonals of 136) and
    ``loop-ut4-t4096``'s layer (4 of 10), each masked tile at 3/4 of a
    tile with steps of half a block and 5/8 with steps of a quarter,
    the kernels' own."""
    from multidisttorch_tpu.ops.pallas_attention import _tile_work

    shapes = [(16384, 4096), (16384, None), (4096, None)]
    assert [len(_visits_of(t, 1024, w)) for t, w in shapes] == [70, 136, 10]
    assert [_tile_work(t, 1024, w) for t, w in shapes] == [59.5, 130.0, 8.5]
    for share, work in ((2, [63.0, 132.0, 9.0]), (4, [59.5, 130.0, 8.5])):
        sub_of(share)
        assert [_tile_work(t, 1024, w) for t, w in shapes] == work
    # a window of blocks and a part keeps its far edges whole: only the diagonals shrink
    assert _tile_work(16384, 1024, 4000) == len(_visits_of(16384, 1024, 4000)) - 16 * 3 / 8


def _visits_of(t, blk, window):
    """The tiles that hold a kept pair, by brute force over each tile's
    spread of ``query - key``: (query block, K/V block, flags), query
    block by query block from the diagonal down, flags first (the
    diagonal), last (the farthest) and masked (a pair not kept)."""
    out = []
    for i in range(t // blk):
        row = []
        for j in range(i, -1, -1):
            low, high = (i - j) * blk - (blk - 1), (i - j) * blk + (blk - 1)
            reach = high if window is None else min(high, window - 1)
            if max(low, 0) <= reach:
                whole = low >= 0 and (window is None or high < window)
                row.append([i, j, 4 * (not whole)])
        row[0][2] |= 1
        row[-1][2] |= 2
        out += [tuple(x) for x in row]
    return out


@pytest.mark.parametrize("t, blk, window", [
    (16384, 512, 512), (16384, 512, None), (8192, 512, None), (1024, 128, 100), (1024, 128, 300),
], ids=lambda x: str(x))
def test_the_64_wide_pair_walks_the_tiles_it_walked(t, blk, window):
    """``_visits``, which the 64-wide pair shares, lists the tiles and
    flags of a brute-force walk at that pair's shapes
    (``ssm-yoco-t16384``'s window of 512 and full layer,
    ``moe-conv-t8192``'s layer) and at small ones."""
    from multidisttorch_tpu.ops.pallas_attention import _visits

    assert list(zip(*(x.tolist() for x in _visits(t, blk, window)))) == _visits_of(t, blk, window)


def test_one_query_head_a_kv_head_with_q_rotated_in_the_kernels():
    """Heads 128 wide, as many KV heads as query heads, no window, q
    handed over unrotated with its angles (the looped model's blocks):
    forward and the gradients of q, k and v against the dense form of q
    rotated."""
    q, k, v, co = _operands(512, 4, 4, seed=5)
    angle = rope_angles(jnp.arange(512), 1e6, 128)
    rotation = jnp.cos(angle), jnp.sin(angle)

    def kernel(q, k, v):
        out = grouped_attention(q, k, v, q_rotation=rotation, block=128)
        return jnp.sum(out * co), out

    def dense(q, k, v):
        out = _dense(rope_halves(q, *rotation), k, v, None)
        return jnp.sum(out * co), out

    with jax.default_matmul_precision("highest"):
        (_, got), got_grads = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(q, k, v)
        (_, want), want_grads = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(q, k, v)
    assert _rel(got, want) < 1e-5
    for have, need in zip(got_grads, want_grads, strict=True):
        assert _rel(have, need) < 1e-5
    assert grouped_takes_kernel("TPU v5 lite", 1, 4096, 16, 16, 128, rotates_q=True)


def test_group_of_one_gives_the_flash_kernel_s_answers():
    """One KV head a query head and no window is what
    ``flash_attention`` computes."""
    from multidisttorch_tpu.ops.pallas_attention import flash_attention

    q, k, v, _ = _operands(256, 2, 2, seed=3)
    with jax.default_matmul_precision("highest"):
        got = grouped_attention(q, k, v, block=128)
        want = flash_attention(q, k, v, causal=True, block=128)
    assert _rel(got, want) < 1e-6


@pytest.mark.parametrize("window", [None, 100], ids=["full", "window"])
def test_the_plain_blocked_path_is_the_dense_form(window):
    q, k, v, co = _operands(256, 4, 2, seed=5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * co)

    with jax.default_matmul_precision("highest"):
        blocked = lambda q, k, v: blocked_window_attention(q, k, v, window=window, block=64)
        got = jax.value_and_grad(loss(blocked), (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(loss(lambda q, k, v: _dense(q, k, v, window)), (0, 1, 2))(q, k, v)
    for have, need in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert _rel(have, need) < 1e-5


def test_refuses_what_does_not_tile():
    q, k, v, _ = _operands(256, 3, 2)
    with pytest.raises(ValueError, match="whole groups"):
        grouped_attention(q, k, v)
    with pytest.raises(ValueError, match="whole groups"):  # 64 wide, an odd KV head left over
        grouped_attention(q[..., :64], k[:, :, :1, :64].repeat(3, 2), v[:, :, :1, :64].repeat(3, 2))
    with pytest.raises(ValueError, match="no block edge"):
        grouped_attention(q[:, :200, :2], k[:, :200], v[:, :200])


def test_rule_takes_one_tpu_chip_at_width_128():
    assert grouped_takes_kernel("TPU v5 lite", 1, 16384, 28, 4, 128)
    assert grouped_takes_kernel("TPU v5 lite", 1, 256, 2, 2, 128)
    assert not grouped_takes_kernel("cpu", 1, 16384, 28, 4, 128)
    assert not grouped_takes_kernel("TPU v5 lite", 4, 16384, 28, 4, 128)
    assert not grouped_takes_kernel("TPU v5 lite", 1, 16384, 28, 4, 32)
    assert not grouped_takes_kernel("TPU v5 lite", 1, 200, 28, 4, 128)
    assert not grouped_takes_kernel("TPU v5 lite", 1, 16384, 28, 8 - 3, 128)


def test_rule_takes_width_64_where_the_kv_heads_pair_up():
    assert grouped_takes_kernel("TPU v5 lite", 1, 16384, 40, 20, 64)
    assert grouped_takes_kernel("TPU v5 lite", 1, 256, 8, 2, 64)
    assert not grouped_takes_kernel("TPU v5 lite", 1, 16384, 40, 5, 64)  # an odd KV head is left over
    assert not grouped_takes_kernel("TPU v5 lite", 1, 16384, 40, 16, 64)  # no whole groups
    assert not grouped_takes_kernel("cpu", 1, 16384, 40, 20, 64)
    assert not grouped_takes_kernel("TPU v5 lite", 4, 16384, 40, 20, 64)
    # the kernels at 64 rotate nothing: a rotary layer (28 heads over 4 of 64 trained on the
    # plain path before them) keeps it, and at 128 the kernels rotate q as they load it
    assert grouped_takes_kernel("TPU v5 lite", 1, 16384, 28, 4, 64)
    assert not grouped_takes_kernel("TPU v5 lite", 1, 16384, 28, 4, 64, rotates_q=True)
    assert grouped_takes_kernel("TPU v5 lite", 1, 16384, 28, 4, 128, rotates_q=True)


def _operands_64(t, h, hkv, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shape = lambda heads: (2, t, heads, 64)
    return (jax.random.normal(keys[0], shape(h)), jax.random.normal(keys[1], shape(hkv)),
            jax.random.normal(keys[2], shape(hkv)), jax.random.normal(keys[3], shape(h)))


# (query heads, KV heads): two, four and one query heads a KV head; four KV heads: two lane blocks
@pytest.mark.parametrize("heads", [(4, 2), (8, 2), (2, 2), (8, 4)], ids=lambda h: f"h{h[0]}kv{h[1]}")
@pytest.mark.parametrize("window", [None, 100, 128, 300], ids=lambda w: f"w{w}")
def test_kernels_at_width_64_match_the_masked_dense_form_and_the_plain_path(heads, window):
    """Forward and the gradients of q, k and v at heads 64 wide, two KV
    heads a lane block, T = 512 as four K/V blocks of 128 (a window
    shorter than a block, a block, several and a part), against the
    masked softmax and ``blocked_window_attention``."""
    q, k, v, co = _operands_64(512, *heads, seed=heads[0])
    loss = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * co), (0, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = loss(lambda q, k, v: grouped_attention(q, k, v, window=window, block=128))(q, k, v)
        want = loss(lambda q, k, v: _dense(q, k, v, window))(q, k, v)
        plain = loss(lambda q, k, v: blocked_window_attention(q, k, v, window=window, block=64))(
            q, k, v)
    for have, need, other in zip(*(jax.tree.leaves(x) for x in (got, want, plain)), strict=True):
        assert _rel(have, need) < 1e-5
        assert _rel(other, need) < 1e-5


# 4 query heads a KV head (``lfm2-24b-a2b``'s 32 over 8), the block edge the
# layer would take: T a multiple of 1,024 (edge 512) and not (640: edge 128)
@pytest.mark.parametrize(
    "heads, t", [((8, 2), 1024), ((8, 2), 640), ((16, 4), 640)],
    ids=["h8kv2-t1024", "h8kv2-t640", "h16kv4-t640"],
)
def test_kernels_at_width_64_with_four_query_heads_a_kv_head(heads, t):
    """Forward and the gradients of q, k and v, full causal attention
    at the edge ``grouped_attention`` chooses by itself, against the
    masked softmax and ``blocked_window_attention``."""
    keys = jax.random.split(jax.random.key(t + heads[0]), 4)
    shape = lambda n: (1, t, n, 64)
    q, k, v, co = (jax.random.normal(key, shape(n))
                   for key, n in zip(keys, (heads[0], heads[1], heads[1], heads[0])))
    loss = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * co), (0, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = loss(lambda q, k, v: grouped_attention(q, k, v, window=None, q_rotation=None))(q, k, v)
        want = loss(lambda q, k, v: _dense(q, k, v, None))(q, k, v)
        plain = loss(lambda q, k, v: blocked_window_attention(q, k, v, window=None))(q, k, v)
    for have, need, other in zip(*(jax.tree.leaves(x) for x in (got, want, plain)), strict=True):
        assert _rel(have, need) < 5e-5  # the first leaf is a float32 sum of T x H x 64 terms
        assert _rel(other, need) < 5e-5


def test_rule_takes_rotary_heads_of_64_that_come_rotated():
    """``lfm2-24b-a2b``'s attention layer, 32 query heads over 8 KV
    heads of 64 at T = 8,192 on one v5e chip: the kernels, since the
    block rotates q itself and asks with ``rotates_q`` false; a caller
    that wants the kernels to rotate keeps the plain path."""
    assert grouped_takes_kernel("TPU v5 lite", 1, 8192, 32, 8, 64)
    assert grouped_takes_kernel("TPU v5 lite", 1, 8192, 32, 8, 64, rotates_q=False)
    assert not grouped_takes_kernel("TPU v5 lite", 1, 8192, 32, 8, 64, rotates_q=True)
    assert not grouped_takes_kernel("TPU v5 lite", 4, 8192, 32, 8, 64)
    assert not grouped_takes_kernel("cpu", 1, 8192, 32, 8, 64)


def test_operands_of_four_query_heads_a_kv_head_at_width_64_lower_for_tpu(monkeypatch):
    # the cell moe-conv-t8192's attention as its block hands it over: 4 x
    # 8,192, 32 query heads over 8 KV heads of 64; forward and backward,
    # interpret mode off. No score matrix, no repeated KV head and no
    # head padded to 128 lanes is made around the kernels.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    part = lambda heads: jax.ShapeDtypeStruct((4, 8192, heads, 64), jnp.bfloat16)
    fwd = lambda q, k, v: grouped_attention(q, k, v, window=None, q_rotation=None)
    bwd = jax.grad(lambda *x: fwd(*x).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    for fn, calls in ((fwd, 1), (bwd, 2)):
        text = jax.jit(fn).trace(part(32), part(8), part(8)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("stablehlo.custom_call @tpu_custom_call") == calls
        assert "grouped64_" in text and "8192x8192" not in text
        assert "tensor<4x8192x32x128xbf16>" not in text
        assert "tensor<4x8192x32x64xbf16>" not in text.split("custom_call")[1]


def test_kernels_at_width_64_take_k_and_v_from_elsewhere():
    """A layer's own q over another's k and v (the cross-attention
    layer's call): the cotangents of k and v from two such calls and
    from the layer that made them add up where the arrays are made."""
    q, k, v, co = _operands_64(256, 4, 2, seed=7)
    q2 = jax.random.normal(jax.random.key(8), q.shape)

    def three_readers(attend):
        def loss(q, q2, k, v):
            own = attend(q, k, v, window=None)
            return jnp.sum((own + attend(q2, k, v, window=None) + attend(own, k, v, window=None)) * co)

        return jax.grad(loss, (0, 1, 2, 3))

    with jax.default_matmul_precision("highest"):
        got = three_readers(lambda *a, **kw: grouped_attention(*a, block=128, **kw))(q, q2, k, v)
        want = three_readers(_dense)(q, q2, k, v)
    for have, need in zip(got, want, strict=True):
        assert _rel(have, need) < 1e-5


def test_block_edge_at_width_64_follows_the_window():
    """Given no block, the edge at width 64 is 512 at most (the chip's
    race) and under a window the largest that does not pass it (a
    window of 512 under a block of 1,024 would multiply twice the tiles
    it needs); heads 128 wide keep the edge they were raced at."""
    from multidisttorch_tpu.ops import pallas_attention

    seen = []
    real = pallas_attention._grouped64
    q, k, v, _ = _operands_64(1024, 4, 2)
    try:
        pallas_attention._grouped64 = lambda q, k, v, scale, window, blk: seen.append(blk) or q
        grouped_attention(q, k, v)
        grouped_attention(q, k, v, window=512)
        grouped_attention(q, k, v, window=100)
        grouped_attention(q, k, v, window=512, block=1024)
    finally:
        pallas_attention._grouped64 = real
    assert seen == [512, 512, 128, 1024]
    with pytest.raises(ValueError, match="come rotated"):
        grouped_attention(q, k, v, q_rotation=(jnp.zeros((1024, 32)), jnp.zeros((1024, 32))))


def test_the_plain_path_reads_only_the_keys_a_window_reaches():
    """No ``(H, block, T)`` array for a window layer off the chip: a
    query block meets ``block + window - 1`` keys, in both passes."""
    part = lambda heads: jax.ShapeDtypeStruct((1, 4096, heads, 64), jnp.float32)
    fn = lambda q, k, v: blocked_window_attention(q, k, v, window=512, block=512)
    grad = jax.grad(lambda *x: fn(*x).sum(), argnums=(0, 1, 2))
    for traced in (fn, grad):
        text = str(jax.make_jaxpr(traced)(part(4), part(2), part(2)))
        assert "512,1023]" in text and "512,4096]" not in text
    full = str(jax.make_jaxpr(lambda q, k, v: blocked_window_attention(q, k, v, block=512))(
        part(4), part(2), part(2)))
    assert "512,4096]" in full


def test_grouped_operands_lower_for_tpu(monkeypatch):
    # the cell moe-swa-t16384's attention as its block hands it over: 1 x
    # 16,384, 28 query heads over 4 KV heads; forward and backward,
    # interpret mode off, a full layer and a window layer whose q the
    # kernels rotate. No score matrix and no repeated KV head is made
    # around the kernels.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    part = lambda heads: jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16)
    angles = jnp.zeros((16384, 64), jnp.float32)
    for window, rotation in ((None, None), (4096, (jnp.cos(angles), jnp.sin(angles)))):
        fwd = lambda q, k, v: grouped_attention(q, k, v, window=window, q_rotation=rotation)
        bwd = jax.grad(lambda *x: fwd(*x).astype(jnp.float32).sum(), argnums=(0, 1, 2))
        for fn, calls in ((fwd, 1), (bwd, 2)):
            text = jax.jit(fn).trace(part(28), part(4), part(4)).lower(
                lowering_platforms=("tpu",)).as_text()
            assert text.count("stablehlo.custom_call @tpu_custom_call") == calls
            assert "16384x16384" not in text
            assert "tensor<1x16384x28x128xbf16>" not in text.split("custom_call")[1]


def test_operands_at_width_64_lower_for_tpu(monkeypatch):
    # the cell ssm-yoco-t16384's attention as its blocks hand it over: 1
    # x 16,384, 40 query heads over 20 KV heads of 64; forward and
    # backward, interpret mode off, a full layer and a window layer. No
    # score matrix, no repeated KV head and no head padded to 128 lanes
    # is made around the kernels.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    part = lambda heads: jax.ShapeDtypeStruct((1, 16384, heads, 64), jnp.bfloat16)
    for window in (None, 512):
        fwd = lambda q, k, v: grouped_attention(q, k, v, window=window)
        bwd = jax.grad(lambda *x: fwd(*x).astype(jnp.float32).sum(), argnums=(0, 1, 2))
        for fn, calls in ((fwd, 1), (bwd, 2)):
            text = jax.jit(fn).trace(part(40), part(20), part(20)).lower(
                lowering_platforms=("tpu",)).as_text()
            assert text.count("stablehlo.custom_call @tpu_custom_call") == calls
            assert "grouped64_" in text and "16384x16384" not in text
            assert "tensor<1x16384x40x128xbf16>" not in text
            assert "tensor<1x16384x40x64xbf16>" not in text.split("custom_call")[1]
