#!/usr/bin/env python3
"""Compile a cell's programs at their real size for a described v5e.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_v5e.py lm-dense lm-short-t256

Run by hand before a chip call (on-chip-measurement guide, section 2):
the TPU compiler refuses here, at no chip time, what it would refuse
there, and ``memory_analysis()`` says whether the step fits 16 GB.
Nothing runs, so this says nothing about results or times. The cache
guard asks jax for the attached TPUs, which do not exist here; this
script stands it down, the program is not changed.
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402

from benchmark import cells  # noqa: E402
from multidisttorch_tpu.utils import compile_cache  # noqa: E402

GIB = 2**30


def main(names: list[str]) -> None:
    compile_cache.guard_submesh = lambda devices: None
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step
    from multidisttorch_tpu.train.steps import TrainState

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    (group,) = setup_groups(1, devices=[topo.devices[0]])
    for name in names:
        cell = cells.load_cell(name)
        entry, ref = cell.entry(), cell.reference()
        model, traffic, config = entry.build_model(cell.config), cell.traffic, cell.config
        tx = optax.adam(traffic["learning_rates"][0])
        repl = group.replicated_sharding

        def shaped(tree, sharding=repl):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
            )

        state = shaped(jax.eval_shape(
            lambda k: create_lm_state(group, model, tx, k), jax.random.key(0)))
        tokens = lambda b: jax.ShapeDtypeStruct(
            (b, traffic["sequence_length"]), jnp.int32, sharding=group.batch_sharding)
        sgd = optax.sgd(1.0)
        probe = TrainState(params=state.params, opt_state=sgd.init(state.params), step=state.step)
        few = tokens(entry.REFERENCE_SEQUENCES)
        programs = {
            "train step": (make_lm_train_step(group, model, tx),
                           (state, tokens(traffic["batch_sequences"]))),
            "probe step (sgd)": (make_lm_train_step(group, model, sgd), (shaped(probe), few)),
            "program logits": (jax.jit(lambda p, t: model.apply({"params": p}, t)),
                               (state.params, few)),
            "reference": (jax.jit(lambda w, t: ref.logits_loss_grads(w, t, config)),
                          (entry.reference_weights(state.params, config["n_layer"]), few)),
        }
        for label, (fn, fn_args) in programs.items():
            t0 = time.perf_counter()
            mem = fn.lower(*fn_args).compile().memory_analysis()
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            print(f"{name}: {label}: compiled in {time.perf_counter() - t0:.1f} s; "
                  f"arguments {mem.argument_size_in_bytes / GIB:.2f} outputs "
                  f"{mem.output_size_in_bytes / GIB:.2f} temporaries "
                  f"{mem.temp_size_in_bytes / GIB:.2f} aliased {mem.alias_size_in_bytes / GIB:.2f} "
                  f"-> {total / GIB:.2f} GiB of 16 GB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or ["lm-dense", "lm-short-t256"])
