#!/usr/bin/env python3
"""The controls of ``ssm-yoco-t16384``'s comparison: one run of the cell
through ``benchmark/run.py`` with the program made worse on purpose,
against the unchanged reference. Each has to come out ``"correct":
false``; ``compared.why`` in ``benchmark/configs/phi-4-mini-flash.json``
quotes what they read.

    python3 benchmark/tests/degrade_ssm.py bits3 --workload ssm-yoco-t16384 --seed 1 --seconds 30

- ``bits3``: the program's matrices rounded to 3 mantissa bits, on the
  program's side of the comparison only (the precision below bf16: every
  limit but the parameters' change has to catch it);
- ``bf16state``: the scan's state rounded to bf16 after every step, in
  ``scan_fwd`` and in ``scan_bwd``'s remake, the window's steps included
  (the scan's own limits have to catch it);
- ``frozen``: the compared step hands back the parameters it was given
  (``param_change_rel_l2`` reads 1).

Run by hand on the chip, through the chip tool; the arguments after the
mode are ``benchmark/run.py``'s. No test collects this file.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MODES = ("bits3", "bf16state", "frozen")


def _on_the_compared_state(change) -> None:
    """``change(trial)`` after the second ``_Trial.init_state`` of the
    run: the first makes the window's state, the second the state
    ``program_side`` steps, the third the reference's weights."""
    from benchmark.entries import lm_trial

    real, calls = lm_trial._Trial.init_state, [0]

    def init_state(self):
        real(self)
        calls[0] += 1
        if calls[0] == 2:
            change(self)

    lm_trial._Trial.init_state = init_state


def bits3() -> None:
    import dataclasses

    import jax

    def change(trial):
        rounded = jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)
            if a.ndim >= 2 else a,
            trial.state.params,
        )
        trial.state = dataclasses.replace(trial.state, params=rounded)

    _on_the_compared_state(change)


def bf16state() -> None:
    import jax.numpy as jnp

    from multidisttorch_tpu.ops import selective_scan

    real = selective_scan._advanced  # a lane tile of the state after one step, both kernels'
    selective_scan._advanced = lambda *a: real(*a).astype(jnp.bfloat16).astype(jnp.float32)


def frozen() -> None:
    import dataclasses

    import jax

    def change(trial):
        step = trial.step

        def hands_back(state, tokens):
            given = jax.device_get(state.params)
            after, metrics = step(state, tokens)
            placed = jax.tree.map(lambda a: a.sharding, after.params)
            for moved in jax.tree.leaves(jax.block_until_ready(after.params)):
                moved.delete()  # the chip has no room for both sets beside the step's state
            return dataclasses.replace(after, params=jax.device_put(given, placed)), metrics

        trial.step = hands_back

    _on_the_compared_state(change)


if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] not in MODES:
        sys.exit(f"usage: degrade_ssm.py {{{'|'.join(MODES)}}} --workload ssm-yoco-t16384 --seed S ...")
    mode = sys.argv.pop(1)
    sys.path.insert(0, ROOT)
    {"bits3": bits3, "bf16state": bf16state, "frozen": frozen}[mode]()
    print(f"[degrade] {mode}", flush=True)
    sys.argv[0] = os.path.join(ROOT, "benchmark", "run.py")
    runpy.run_path(sys.argv[0], run_name="__main__")
