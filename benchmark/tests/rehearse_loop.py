#!/usr/bin/env python3
"""Compile ``loop-ut4-t4096``'s programs at their real size for a
described v5e: ``rehearse_conv.py`` for the looped entry
(``looped_lm_trial``, whose ``reference_weights`` takes the parameters
alone).

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_loop.py [step|apply|reference|logits ...]

Run by hand before a run on the chip:
the TPU compiler refuses here, at no chip time, what it would refuse
there, ``memory_analysis()`` says whether each program fits 16 GB, and
the step's text says whether the blocks run the 128-wide grouped kernel
pair. Nothing runs.
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
CELL = "loop-ut4-t4096"


def main(which: list[str]) -> None:
    compile_cache.guard_submesh = lambda devices: None
    from multidisttorch_tpu.parallel.mesh import setup_groups
    from multidisttorch_tpu.train.lm import create_lm_state, make_lm_train_step

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    (group,) = setup_groups(1, devices=[topo.devices[0]])
    cell = cells.load_cell(CELL)
    entry, ref = cell.entry(), cell.reference()
    model, traffic, config = entry.build_model(cell.config), cell.traffic, cell.config
    tx = optax.adam(traffic["learning_rates"][0])

    def shaped(tree, sharding=group.replicated_sharding):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )

    state = shaped(jax.eval_shape(lambda k: create_lm_state(group, model, tx, k), jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct(
        (traffic["batch_sequences"], traffic["sequence_length"]), jnp.int32,
        sharding=group.batch_sharding,
    )
    weights = entry.reference_weights(state.params)
    state_of_a_loop = jax.ShapeDtypeStruct(
        (*tokens.shape, config["hidden_size"]), jnp.float32, sharding=group.replicated_sharding
    )
    programs = {
        "step": (make_lm_train_step(group, model, tx), (state, tokens)),
        "apply": (jax.jit(lambda p, t: model.apply({"params": p}, t)[0]), (state.params, tokens)),
        "reference": (jax.jit(lambda w, t: ref.hidden_loss_grads(w, t, config)), (weights, tokens)),
        "logits": (jax.jit(ref.logits_of), (state_of_a_loop, weights)),
    }
    for label in which or list(programs):
        fn, fn_args = programs[label]
        t0 = time.perf_counter()
        compiled = fn.lower(*fn_args).compile()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{CELL}: {label}: compiled in {time.perf_counter() - t0:.1f} s; "
              f"arguments {mem.argument_size_in_bytes / GIB:.2f} outputs "
              f"{mem.output_size_in_bytes / GIB:.2f} temporaries "
              f"{mem.temp_size_in_bytes / GIB:.2f} aliased {mem.alias_size_in_bytes / GIB:.2f} "
              f"-> {total / GIB:.2f} GiB of 16 GB", flush=True)
        if label == "step":
            text = compiled.as_text()
            calls = {name: text.count(f"/{name}\"") + text.count(f"/{name}/")
                     for name in ("grouped_fwd", "grouped_bwd")}
            print(f"{CELL}: step: {text.count('tpu_custom_call')} kernel calls, op names holding "
                  f"{calls}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
