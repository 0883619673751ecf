#!/usr/bin/env python
"""The quickest proof that the trial path still starts on the chip.

    python chip_smoke.py

One process drives the system's main path once through the entry points
a user calls — ``TrialConfig`` into ``run_hpo`` (classic, then stacked),
the multi-chip trial modes, the sweep service, the Pallas kernels and
the LM step — at the flagship VAE's full width (784-400-20, batch 128)
on MNIST-sized synthetic data, and checks what comes out by the repo's
own means: statuses, finite falling losses, placement, parity with the
plain-XLA references. It refuses any backend but ``tpu``, a phase that
fails ends the run with its traceback, and the last line of stdout is
one JSON object naming the device as jax reports it.

The phases are plain functions of the devices (and a size) so the CPU
test suite can run their control flow at a tiny size
(``tests/test_chip_smoke.py``); only :func:`main` insists on the chip.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from multidisttorch_tpu.data import native  # noqa: E402
from multidisttorch_tpu.data.datasets import synthetic_mnist  # noqa: E402
from multidisttorch_tpu.hpo import TrialConfig, run_hpo  # noqa: E402
from multidisttorch_tpu.models.vae import VAE  # noqa: E402
from multidisttorch_tpu.parallel.mesh import setup_groups  # noqa: E402
from multidisttorch_tpu.train import ckpt_store  # noqa: E402
from multidisttorch_tpu.utils.compile_cache import (  # noqa: E402
    compile_log,
    enable_compile_cache,
)


@dataclass(frozen=True)
class Size:
    """What the phases train. The default is the flagship at full
    width on MNIST-sized data (468 optimizer steps an epoch); the CPU
    test passes a tiny one."""

    hidden_dim: int = 400
    latent_dim: int = 20
    batch_size: int = 128
    fused_steps: int = 10
    train_rows: int = 60000
    test_rows: int = 10000
    stacked_lanes: int = 8
    submissions: int = 4
    # Bound on |stacked lane − classic trial| / |classic| for the final
    # losses. The two run different programs (K vmapped lanes against
    # one trial), bit-identical on XLA:CPU (tests/test_stacking.py) but
    # not on the chip, where f32 matmuls take bf16 passes and the
    # tilings differ; the smoke prints the deviation it saw.
    stacked_rel_tol: float = 1e-3

    def config(self, trial_id: int, **kw) -> TrialConfig:
        base = dict(
            trial_id=trial_id,
            epochs=1,
            batch_size=self.batch_size,
            hidden_dim=self.hidden_dim,
            latent_dim=self.latent_dim,
            fused_steps=self.fused_steps,
            log_interval=100,
            seed=trial_id,
            lr=1e-3 * (1 + trial_id % 4),
        )
        base.update(kw)
        return TrialConfig(**base)

    def steps_per_epoch(self) -> int:
        return self.train_rows // self.batch_size


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str, devices):
    """Announce a phase with the device it runs on; report its wall
    time (and, from the program's compile log, its compile share) when
    it returns. An exception passes through: a failed phase fails the
    run."""
    d0 = devices[0]
    where = (
        f"platform={d0.platform} device_kind={d0.device_kind!r} "
        f"devices={len(devices)}"
    )
    say(f"{name}: start {where}")
    log = compile_log()  # installed by enable_compile_cache()
    before = log.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    after = log.snapshot()
    say(
        f"{name}: ok wall_s={wall:.2f} compile_s="
        f"{after['backend_s'] - before['backend_s']:.2f} cache_hits="
        f"{after['hits'] - before['hits']} cache_misses="
        f"{after['misses'] - before['misses']} {where}",
    )


def check_results(results, *, steps: int, label: str) -> None:
    for r in results:
        check(
            r.status == "completed",
            f"{label}: trial {r.trial_id} status={r.status!r} {r.error}",
        )
        check(
            r.steps == steps,
            f"{label}: trial {r.trial_id} ran {r.steps} steps, want {steps}",
        )
        check(
            np.isfinite(r.final_train_loss) and np.isfinite(r.final_test_loss),
            f"{label}: trial {r.trial_id} loss not finite",
        )
        check(
            r.checkpoint and os.path.exists(r.checkpoint),
            f"{label}: trial {r.trial_id} wrote no checkpoint",
        )


def check_placement(result, group, size: Size) -> None:
    """The trial's saved state, restored through the public checkpoint
    API, lives on exactly its own submesh's devices."""
    from multidisttorch_tpu.train.checkpoint import restore_state
    from multidisttorch_tpu.train.steps import create_train_state

    cfg = result.config
    model = VAE(hidden_dim=size.hidden_dim, latent_dim=size.latent_dim)
    template = create_train_state(
        group, model, optax.adam(cfg.lr), jax.random.key(cfg.seed)
    )
    state = restore_state(template, result.checkpoint, group)
    want = set(group.devices)
    for leaf in jax.tree.leaves(state.params):
        check(
            leaf.sharding.device_set == want,
            f"trial {result.trial_id}: params on "
            f"{sorted(d.id for d in leaf.sharding.device_set)}, submesh is "
            f"{sorted(d.id for d in want)}",
        )
        check(
            bool(jnp.all(jnp.isfinite(leaf))),
            f"trial {result.trial_id}: non-finite params",
        )
    check(
        int(state.step) == result.steps,
        f"trial {result.trial_id}: checkpoint at step {int(state.step)}, "
        f"trial ran {result.steps}",
    )
    say(
        f"  trial {result.trial_id}: state on devices "
        f"{sorted(d.id for d in want)} (group {group.group_id})",
    )


def phase_classic(devices, out_dir: str, size: Size, train, test) -> list:
    """``run_hpo``, one trial per chip; then ``resume=True`` for one
    more epoch from the v2 checkpoints it saved."""
    n = len(devices)
    out_dir = os.path.join(out_dir, "classic")
    groups = setup_groups(n, devices=devices)
    configs = [size.config(i) for i in range(n)]
    spe = size.steps_per_epoch()
    first = run_hpo(configs, train, test, groups=groups, out_dir=out_dir)
    check_results(first, steps=spe, label="classic")
    for r, g in zip(first, groups):
        check(
            r.group_id == g.group_id,
            f"trial {r.trial_id} ran on group {r.group_id}",
        )
        with open(r.checkpoint, "rb") as f:
            check(ckpt_store.is_manifest_blob(f.read()), "checkpoint is not v2")
        check_placement(r, g, size)
    more = [size.config(i, epochs=2) for i in range(n)]
    second = run_hpo(
        more, train, test, groups=groups, out_dir=out_dir, resume=True
    )
    check_results(second, steps=2 * spe, label="classic resume")
    for a, b in zip(first, second):
        check(
            b.resumed_from_step == a.steps,
            f"trial {b.trial_id} resumed from step {b.resumed_from_step}, "
            f"want {a.steps}",
        )
        check(
            b.final_train_loss < a.final_train_loss,
            f"trial {b.trial_id}: loss did not fall "
            f"({a.final_train_loss:.4f} -> {b.final_train_loss:.4f})",
        )
        say(
            f"  trial {b.trial_id} group {b.group_id}: train loss "
            f"{a.final_train_loss:.4f} -> {b.final_train_loss:.4f}, test "
            f"{b.final_test_loss:.4f}, {b.steps} steps",
        )
    path = "native" if native.available() else "numpy"
    say(f"  input path: {path}")
    return first


def phase_stacked(devices, out_dir: str, size: Size, train, test) -> list:
    """``run_hpo(stack_trials=True)``: the lanes complete, and lane 0
    lands where the same config lands on the classic path. The stacked
    RNG stream is the classic per-step one (docs/STACKING.md), so the
    classic twin runs ``fused_steps=1``."""
    groups = setup_groups(len(devices), devices=devices)
    configs = [size.config(i) for i in range(size.stacked_lanes)]
    spe = size.steps_per_epoch()
    stacked = run_hpo(
        configs, train, test, groups=groups,
        out_dir=os.path.join(out_dir, "stacked"), stack_trials=True,
    )
    check_results(stacked, steps=spe, label="stacked")
    check(all(r.stacked for r in stacked), "a lane ran unstacked")
    check(
        stacked[0].final_train_loss != stacked[1].final_train_loss,
        "lanes 0 and 1 differ in lr and seed but not in loss",
    )
    (twin,) = run_hpo(
        [size.config(0, fused_steps=1)], train, test, groups=groups[:1],
        out_dir=os.path.join(out_dir, "twin"),
    )
    check_results([twin], steps=spe, label="classic twin")
    for name in ("final_train_loss", "final_test_loss"):
        lane, ref = getattr(stacked[0], name), getattr(twin, name)
        rel = abs(lane - ref) / abs(ref)
        say(
            f"  lane 0 vs classic {name}: {lane:.6f} vs {ref:.6f} "
            f"(rel {rel:.2e})",
        )
        check(
            rel <= size.stacked_rel_tol,
            f"stacked lane 0 {name} {lane} is {rel:.2e} off classic {ref}",
        )
    return stacked


def phase_multichip(devices, out_dir: str, size: Size, train, test) -> None:
    """Trials that span chips: two-chip data-parallel trials (the
    gradient psum crosses the interconnect), the subgroup gather, a
    ZeRO-sharded update, then every parallel mode of the dry run."""
    import __graft_entry__ as graft
    from multidisttorch_tpu.parallel.collectives import group_all_gather

    devices = list(devices)[:4]
    groups = setup_groups(2, devices=devices)
    for g, want in zip(groups, ([0, 1], [2, 3])):
        ranks = jnp.array(g.global_ranks, jnp.int32)
        got = [int(x) for x in group_all_gather(g, ranks)]
        say(f"  subgroup {g.group_id} gathered: {got}")
        check(got == want, f"subgroup {g.group_id} gathered {got}, want {want}")

    spe = size.steps_per_epoch()
    dp = run_hpo(
        [size.config(i) for i in range(2)], train, test, groups=groups,
        out_dir=os.path.join(out_dir, "dp2"),
    )
    check_results(dp, steps=spe, label="2-chip data-parallel")
    for r, g in zip(dp, groups):
        check_placement(r, g, size)

    (zero,) = run_hpo(
        [size.config(0, zero_update=True)], train, test, groups=groups[:1],
        out_dir=os.path.join(out_dir, "zero"),
    )
    check_results([zero], steps=spe, label="zero_update")
    check(
        0 < zero.optimizer_state_bytes < dp[0].optimizer_state_bytes,
        f"zero_update holds {zero.optimizer_state_bytes} optimizer bytes a "
        f"device, replicated holds {dp[0].optimizer_state_bytes}",
    )

    graft.dryrun_multichip(4, devices=devices)


def phase_service(devices, out_dir: str, size: Size, train, test) -> dict:
    """A ``SweepService`` over a fresh directory, submissions through
    ``SweepClient``, served until drained."""
    from multidisttorch_tpu.service.queue import SweepClient
    from multidisttorch_tpu.service.runtime import SweepService

    service_dir = os.path.join(out_dir, "service")
    client = SweepClient(service_dir, tenant="smoke")
    ids = [
        client.submit(
            dict(
                epochs=1, batch_size=size.batch_size,
                hidden_dim=size.hidden_dim, latent_dim=size.latent_dim,
                fused_steps=size.fused_steps, seed=i,
            )
        )
        for i in range(size.submissions)
    ]
    svc = SweepService(
        service_dir, devices=list(devices), train_data=train, test_data=test
    )
    report = svc.serve(exit_when_drained=True, max_wall_s=600)
    check(
        report["outcome"] == "idle",
        f"service ended {report['outcome']!r}, not drained",
    )
    settled = report["settled"]
    say(f"  settled: {settled}")
    check(
        sorted(settled) == sorted(ids),
        f"settled {sorted(settled)}, submitted {sorted(ids)}",
    )
    check(
        set(settled.values()) == {"completed"},
        f"not every submission completed: {settled}",
    )
    return settled


def _close(got, want, *, rtol, atol, what: str) -> None:
    """``assert_allclose`` in f32 (numpy has no bf16); NaNs fail it."""
    np.testing.assert_allclose(
        np.asarray(jnp.asarray(got, jnp.float32)),
        np.asarray(jnp.asarray(want, jnp.float32)),
        rtol=rtol, atol=atol, err_msg=what,
    )


def _kernel_elbo(batch: int, dtype) -> None:
    from multidisttorch_tpu.ops.losses import elbo_loss_sum
    from multidisttorch_tpu.ops.pallas_elbo import fused_elbo_loss_sum

    rng = np.random.default_rng(batch)
    logits = jnp.asarray(rng.normal(0, 2, (batch, 784)), dtype)
    x = jnp.asarray(rng.uniform(0, 1, (batch, 784)), jnp.float32)
    mu = jnp.asarray(rng.normal(0, 1, (batch, 20)), dtype)
    logvar = jnp.asarray(rng.normal(0, 0.5, (batch, 20)), dtype)
    up = lambda a: a.astype(jnp.float32)

    fused = jax.jit(jax.value_and_grad(
        lambda l, m, lv: fused_elbo_loss_sum(l, x, m, lv, 2.0), argnums=(0, 1, 2)
    ))
    plain = jax.jit(jax.value_and_grad(
        lambda l, m, lv: elbo_loss_sum(l, x, m, lv, 2.0), argnums=(0, 1, 2)
    ))
    val, grads = fused(logits, mu, logvar)
    ref, ref_grads = plain(up(logits), up(mu), up(logvar))
    what = f"fused ELBO batch={batch} {jnp.dtype(dtype).name}"
    _close(val, ref, rtol=1e-5, atol=0, what=what + " value")
    gtol = 1e-5 if dtype == jnp.float32 else 1e-2  # bf16 cotangents
    for g, r, primal in zip(grads, ref_grads, (logits, mu, logvar)):
        check(g.dtype == primal.dtype, f"{what}: cotangent dtype {g.dtype}")
        _close(g, r, rtol=gtol, atol=gtol, what=what + " grad")


def _kernel_flash(b: int, t: int, h: int, d: int, dtype, dv: int | None = None) -> None:
    """``d``: the width of q and k, and of v unless ``dv`` gives v its own."""
    from multidisttorch_tpu.ops.pallas_attention import flash_attention
    from multidisttorch_tpu.ops.ring_attention import dense_attention_reference

    dv = d if dv is None else dv
    q, k, v = (
        jax.random.normal(kk, (b, t, h, width), jnp.float32).astype(dtype)
        for kk, width in zip(jax.random.split(jax.random.key(t), 3), (d, d, dv))
    )
    w = jax.random.normal(jax.random.key(1), (b, t, h, dv), jnp.float32)

    def run(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(jnp.float32) * w)

        return jax.jit(lambda q, k, v: (attn(q, k, v, causal=True),
                                        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

    with jax.default_matmul_precision("highest"):
        out, grads = run(flash_attention)(q, k, v)
        ref, ref_grads = run(dense_attention_reference)(q, k, v)
    what = f"flash attention B={b} T={t} H={h} d={d}/{dv} {jnp.dtype(dtype).name}"
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    _close(out, ref, rtol=tol, atol=tol, what=what + " forward")
    for g, r in zip(grads, ref_grads):
        _close(g, r, rtol=tol, atol=tol * 4, what=what + " backward")


def _kernel_ring_flash(devices) -> None:
    from multidisttorch_tpu.ops.pallas_attention import make_ring_flash_attention
    from multidisttorch_tpu.ops.ring_attention import dense_attention_reference

    (trial,) = setup_groups(1, devices=list(devices)[:4])
    q, k, v = (
        jax.random.normal(kk, (2, 1024, 4, 64), jnp.float32)
        for kk in jax.random.split(jax.random.key(9), 3)
    )
    ring = make_ring_flash_attention(trial, causal=True)
    dense = lambda q, k, v: dense_attention_reference(q, k, v, causal=True)

    def out_and_grads(attn):
        loss = lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)
        return attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        out, grads = out_and_grads(ring)
        ref, ref_grads = out_and_grads(dense)
    _close(out, ref, rtol=2e-4, atol=2e-4, what="ring-flash forward")
    for g, r in zip(grads, ref_grads):
        _close(g, r, rtol=2e-4, atol=8e-4, what="ring-flash backward")


# A small LM for the smoke: the width and depth the round-4 LM runs
# used; the benchmark's cells hold the published sizes.
LM = dict(vocab_size=32768, d_model=512, num_heads=8, num_layers=8, max_len=512)
LM_BATCH, LM_STEPS = 16, 4


def _lm_steps(devices, attention) -> np.ndarray:
    from multidisttorch_tpu.models.transformer import TransformerLM
    from multidisttorch_tpu.train.lm import (
        create_lm_state,
        lm_chunk_sharding,
        make_lm_multi_step,
    )

    (trial,) = setup_groups(1, devices=list(devices)[:1])
    model = TransformerLM(**LM, dtype=jnp.bfloat16, attention=attention)
    tx = optax.adam(1e-3)
    chunks = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(
                0, LM["vocab_size"], (LM_STEPS, LM_BATCH, LM["max_len"]),
                dtype=np.int32,
            )
        ),
        lm_chunk_sharding(trial),
    )
    state = create_lm_state(
        trial, model, tx, jax.random.key(0), example_len=LM["max_len"]
    )
    state, metrics = make_lm_multi_step(trial, model, tx)(state, chunks)
    losses = np.asarray(metrics["loss"], np.float32)
    check(int(state.step) == LM_STEPS, f"LM ran {int(state.step)} steps")
    check(np.all(np.isfinite(losses)), f"LM losses not finite: {losses}")
    return losses


def phase_kernels(devices) -> None:
    """Every Pallas kernel the package exports, compiled, forward and
    backward, f32 and bf16, at the shapes its callers use, against its
    plain-XLA reference; then the LM step with dense attention, with
    the flash kernel injected and with nothing injected, which on one
    chip is the kernel too."""
    from multidisttorch_tpu.ops.pallas_attention import make_flash_attention
    from multidisttorch_tpu.ops.ring_attention import dense_attention_reference

    for dtype in (jnp.float32, jnp.bfloat16):
        _kernel_elbo(128, dtype)  # the flagship's loss call
        _kernel_elbo(4096, dtype)  # multi-block grid, SMEM accumulator
        _kernel_flash(16, 512, 8, 64, dtype)  # the LM bench shape
        _kernel_flash(2, 2048, 8, 64, dtype)  # T > 1024: two blocks of 1,024
        _kernel_flash(2, 1100, 4, 64, dtype)  # causal pad to 1152
        _kernel_flash(2, 200, 4, 64, dtype)  # one whole-sequence block
        _kernel_flash(2, 1024, 4, 128, dtype)  # one head a lane block
        _kernel_flash(2, 2048, 4, 192, dtype, dv=128)  # latent attention: q, k padded to 256
    _kernel_flash(16, 1024, 16, 64, jnp.bfloat16)  # the cell lm-dense
    _kernel_flash(64, 256, 16, 64, jnp.bfloat16)  # the cell lm-short-t256
    _kernel_flash(1, 4096, 8, 192, jnp.bfloat16, dv=128)  # the cell moe-mla-t4096, 8 of its heads
    say("  fused ELBO and flash attention match their references")
    if len(devices) >= 4:
        _kernel_ring_flash(devices)
        say("  ring-flash on 4 chips matches dense")
    # With nothing injected a one-chip model takes the kernel by itself
    # (ops/attention.py::causal), so the dense side names
    # the dense function.
    dense = _lm_steps(
        devices, lambda q, k, v: dense_attention_reference(q, k, v, causal=True)
    )
    flash = _lm_steps(devices, make_flash_attention(causal=True))
    default = _lm_steps(devices, None)
    say(f"  LM losses dense {dense.tolist()} flash {flash.tolist()}")
    check(dense[-1] < dense[0], f"LM loss did not fall: {dense}")
    _close(flash, dense, rtol=2e-2, atol=0, what="LM loss, flash vs dense attention")
    check(
        np.array_equal(default, flash),
        f"LM loss, no attention injected {default.tolist()} vs flash {flash.tolist()}: "
        "on one chip both are the kernel",
    )


def phase_backend() -> list:
    """Versions and devices; the device kind must be one the peak table
    knows."""
    import importlib.metadata as md

    import jaxlib
    from multidisttorch_tpu.telemetry.device import (
        peak_flops_per_chip,
        peak_membw_per_chip,
    )

    devices = jax.devices()
    say(
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {md.version('libtpu')}",
    )
    for d in devices:
        say(f"  {d!r} platform={d.platform} device_kind={d.device_kind!r}")
    kind = devices[0].device_kind
    flops, bw = peak_flops_per_chip(kind), peak_membw_per_chip(kind)
    check(flops and bw, f"device_kind {kind!r} has no peak on record")
    say(f"  peak table: {kind!r} -> {flops:.3g} FLOP/s, {bw:.3g} B/s")
    return devices


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main() -> int:
    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke: needs a TPU, jax found {jax.default_backend()!r} "
            f"({jax.devices()}); there is no CPU path",
            file=sys.stderr,
        )
        return 2
    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries_before} entries)")
    with phase("1 backend", jax.devices()):
        devices = phase_backend()
    size = Size()
    train = synthetic_mnist(size.train_rows, seed=0)
    test = synthetic_mnist(size.test_rows, seed=1)
    # Trial directories, checkpoints and the service's state: some
    # hundred MB nobody reads after the checks, so they go with the run.
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as out_dir:
        with phase("2 run_hpo classic", devices):
            phase_classic(devices, out_dir, size, train, test)
        with phase("3 run_hpo stacked", devices):
            phase_stacked(devices, out_dir, size, train, test)
        if len(devices) >= 4:
            with phase("4 multi-chip trials", devices):
                phase_multichip(devices, out_dir, size, train, test)
        else:
            say(f"4 multi-chip trials: needs 4 devices, have {len(devices)}")
        with phase("5 sweep service", devices):
            phase_service(devices, out_dir, size, train, test)
    with phase("6 kernels", devices):
        phase_kernels(devices)
    entries_after = cache_entries(cache_dir)
    total = compile_log().snapshot()
    say(
        f"7 cache: {cache_dir} entries {entries_before} -> "
        f"{entries_after}; this run hits={total['hits']} misses="
        f"{total['misses']} compile_s={total['backend_s']:.2f} still_on="
        f"{jax.config.jax_enable_compilation_cache} total_wall_s="
        f"{time.perf_counter() - t_start:.1f}",
    )
    d0 = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
