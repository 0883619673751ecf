"""``ops/selective_scan.py`` against a step-by-step float32 loop:
values, the last state and all seven gradients, the ``jax.lax`` form at
chunk lengths that do and do not divide T and the kernel pair
interpreted (``MDT_PALLAS_INTERPRET=1``; the scan asks its own rule, so
the tests tell it the operands lie on one v5e chip), and that the
kernels lower for the TPU at the benchmark cell's size. Nothing is
timed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import selective_scan as ss
from multidisttorch_tpu.ops.selective_scan import scan_takes_kernel, selective_scan

NAMES = ("x", "delta", "A", "B", "C", "D", "delta_bias")


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _loop(x, delta, a, b, c, d, bias):
    """The recurrence a step at a time on the ``(B, E, N)`` state."""
    steps = jax.nn.softplus(delta + bias)

    def step(h, at):
        x_t, d_t, b_t, c_t = at
        h = jnp.exp(d_t[..., None] * a) * h + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("ben,bn->be", h, c_t) + d * x_t

    in_time = lambda z: z.transpose(1, 0, 2)
    h, y = jax.lax.scan(
        step, jnp.zeros((x.shape[0], *a.shape)), tuple(in_time(z) for z in (x, steps, b, c))
    )
    return in_time(y), h


def _operands(t, e, n, seed=0, batch=2):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.normal(0, 1, shape), jnp.float32)
    return (f(batch, t, e), f(batch, t, e) - 2, -jnp.exp(0.5 * f(e, n)), f(batch, t, n),
            f(batch, t, n), f(e), 0.3 * f(e))


def _loss(fn):
    def loss(*operands):
        y, last = fn(*operands)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=jnp.float32).reshape(y.shape))) \
            + jnp.sum(jnp.square(last))

    return jax.value_and_grad(loss, argnums=tuple(range(7)))


def _kernels(fn, *operands):
    return str(jax.make_jaxpr(fn)(*operands)).count("pallas_call")


def _check(t, e, n, chunk, kernels):
    operands = _operands(t, e, n)
    scan = lambda *o: selective_scan(*o, chunk=chunk, return_last_state=True)
    assert _kernels(scan, *operands) == kernels
    np.testing.assert_allclose(scan(*operands)[0], _loop(*operands)[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(scan(*operands)[1], _loop(*operands)[1], rtol=2e-5, atol=2e-5)
    (got, got_grads), (want, want_grads) = _loss(scan)(*operands), _loss(_loop)(*operands)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for name, have, need in zip(NAMES, got_grads, want_grads, strict=True):
        assert _rel(have, need) < 2e-6, (name, _rel(have, need))


@pytest.mark.parametrize("t, chunk", [(48, 16), (40, 16), (24, 64), (33, 8)],
                         ids=["divides", "remainder", "one-chunk", "odd"])
def test_lax_form_is_the_step_by_step_loop(t, chunk):
    _check(t, 8, 4, chunk, kernels=0)


@pytest.mark.parametrize("t, e, n, chunk", [(64, 512, 16, 32), (32, 1024, 8, 16), (16, 512, 16, 16)],
                         ids=["two-chunks", "two-blocks-n8", "one-chunk"])
def test_kernel_pair_is_the_step_by_step_loop(as_v5e, t, e, n, chunk):
    _check(t, e, n, chunk, kernels=1)


def test_low_precision_operands_and_no_last_state():
    """``y`` alone comes back; bf16 operands give bf16 results and
    gradients from a float32 state."""
    x, delta, a, b, c, d, bias = _operands(32, 8, 4, seed=3)
    want, _ = _loop(x, delta, a, b, c, d, bias)
    np.testing.assert_allclose(selective_scan(x, delta, a, b, c, d, bias, chunk=8), want,
                               rtol=2e-5, atol=2e-5)
    half = lambda z: z.astype(jnp.bfloat16)
    low = (half(x), half(delta), a, half(b), half(c), d, bias)
    got = selective_scan(*low, chunk=8)
    assert got.dtype == jnp.bfloat16 and _rel(got.astype(jnp.float32), want) < 3e-2
    grads = jax.grad(lambda *o: selective_scan(*o, chunk=8).astype(jnp.float32).sum(), (0, 1, 3))(*low)
    assert {g.dtype for g in grads} == {jnp.dtype(jnp.bfloat16)}


def test_the_kept_names_spare_the_recomputed_scan(as_v5e):
    """Under the models' remat policy the scan's output and chunk states
    are kept, so the gradient holds one forward and one backward scan;
    under a bare ``jax.checkpoint`` the forward runs again."""
    operands = _operands(32, 512, 8, seed=5, batch=1)

    def count(policy):
        scan = jax.checkpoint(
            lambda *o: selective_scan(*o, chunk=16), policy=policy
        )
        jaxpr = jax.make_jaxpr(jax.grad(lambda *o: scan(*o).sum(), argnums=(0, 1)))(*operands)
        return str(jaxpr).count("pallas_call")

    assert count(decoder._KEEP_ACROSS_REMAT) == 2
    assert count(None) == 3


def test_rule_takes_one_tpu_chip_in_whole_chunks_and_blocks(as_v5e):
    assert scan_takes_kernel("TPU v5 lite", 1, 16384, 5120, 16)
    assert not scan_takes_kernel("cpu", 1, 16384, 5120, 16)
    assert not scan_takes_kernel("TPU v5 lite", 4, 16384, 5120, 16)
    assert not scan_takes_kernel("TPU v5 lite", 1, 16384 + 128, 5120, 16)
    assert not scan_takes_kernel("TPU v5 lite", 1, 16384, 128, 16)
    assert not scan_takes_kernel("TPU v5 lite", 1, 16384, 5120, 4)
    assert scan_takes_kernel("TPU v5 lite", 1, 48, 512, 8, chunk=16)
    assert not scan_takes_kernel("TPU v5 lite", 1, 40, 512, 8, chunk=16)
    assert not scan_takes_kernel("TPU v5 lite", 1, 48, 512, 8, chunk=12)  # half a sublane tile
    # what the kernels do not tile runs the plain form on the chip too
    scan = lambda *o: selective_scan(*o, chunk=16)
    assert _kernels(scan, *_operands(48, 512, 8)) == 1
    assert _kernels(scan, *_operands(40, 512, 8)) == 0


def test_scan_operands_lower_for_tpu(monkeypatch, as_v5e):
    # the cell ssm-yoco-t16384's scan as its block hands it over: 1 x
    # 16,384 x 5,120, a state of 16, bf16 operands; interpret mode off.
    # One kernel forward, two with the backward; no (T, E, N) array
    # around them.
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    t, e, n = 16384, 5120, 16
    bf, f32 = jnp.bfloat16, jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, t, e), bf), ((1, t, e), bf), ((e, n), f32), ((1, t, n), bf), ((1, t, n), bf),
        ((e,), f32), ((e,), f32))]
    fwd = selective_scan
    bwd = jax.grad(lambda *o: fwd(*o).astype(f32).sum(), argnums=tuple(range(7)))
    for fn, calls in ((fwd, 1), (bwd, 2)):
        text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("stablehlo.custom_call @tpu_custom_call") == calls
        assert f"{t}x{e}x{n}" not in text and f"{e}x{n}x{t}" not in text
    assert ss.CHUNK == 256  # 64 chunk states of (16, 5120) float32: 21 MB a layer
