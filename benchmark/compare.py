"""The comparison that decides ``correct``.

Relative errors of the program's outputs against a configuration's
plain reference, held to the tolerances written in the configuration's
file under ``compared``. Arrays stay on the device; only the error
figures come back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def rel_rms(got, want):
    """``rms(got - want) / rms(want)``, in float32."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.sqrt(jnp.mean(jnp.square(got - want)) / jnp.mean(jnp.square(want)))


def tree_rel_l2(got, want) -> dict[str, float]:
    """``|got - want| / |want|`` for every leaf of two like trees, by
    the leaf's path: the gradient norms layer by layer."""
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    flat_got = jax.tree.leaves(got)
    if len(flat_got) != len(flat_want):
        raise ValueError("the two trees have different structure")
    errs = [rel_rms(g, w) for g, (_, w) in zip(flat_got, flat_want)]
    return {
        jax.tree_util.keystr(path): float(e)
        for (path, _), e in zip(flat_want, errs)
    }


def tree_rms(tree) -> dict[str, float]:
    """Root mean square of every leaf, by the leaf's path."""
    rms = jax.jit(lambda a: jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32)))))
    return {
        jax.tree_util.keystr(path): float(rms(leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def verdict(errors: dict[str, float], tolerances: dict) -> tuple[bool, list[str]]:
    """``errors`` maps a tolerance's name to the worst error measured
    for it. A figure that is not finite fails like one that is too
    large."""
    notes, ok = [], True
    for name, worst in errors.items():
        bound = tolerances[name]
        good = worst == worst and worst <= bound
        ok &= good
        notes.append(f"{name}={worst:.3e} (tolerance {bound:g}) {'ok' if good else 'FAILED'}")
    return ok, notes
