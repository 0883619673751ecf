"""Pipeline-parallel TransformerLM: blocks staged over the pipe axis.

Completes the per-family parallelism matrix (the reference has no PP at
all — SURVEY.md §2c): the LM's transformer blocks ride the
shape-heterogeneous GPipe schedule (``parallel/pipeline.py``) as equal-
width stages, with the embedding lookup before the pipeline and the
final-norm + vocab head after it (both are resident on every device —
they're cheap next to the block stack, and keeping them outside lets
the staged bodies stay pure float-array functions, which is the
pipeline's contract). Attention inside a stage must be collective-free:
the dense default or the single-chip flash kernel — NOT a device ring
(a ring inside a ``lax.switch`` branch would need collectives only some
devices execute).

On a ``(data x pipe)`` trial mesh one jitted step trains DP x PP; grads
flow through the packed stage array and the embed/head params alike, so
a single Adam update covers the whole model.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.models.transformer import Block, TransformerLM
from multidisttorch_tpu.parallel.mesh import TrialMesh
from multidisttorch_tpu.parallel.pipeline import (
    pipeline_apply_stages,
    stage_params_sharding,
)


def _stage_layers(num_layers: int, num_stages: int) -> list[list[int]]:
    """Contiguous, near-even block chunks; every stage non-empty."""
    if num_layers < num_stages:
        raise ValueError(
            f"{num_layers} blocks cannot fill {num_stages} pipeline stages"
        )
    base, rem = divmod(num_layers, num_stages)
    out, i = [], 0
    for s in range(num_stages):
        n = base + (1 if s < rem else 0)
        out.append(list(range(i, i + n)))
        i += n
    return out


def make_pipelined_lm(
    trial: TrialMesh,
    model: TransformerLM,
    params: Any,
    *,
    num_microbatches: int,
    attention: Optional[Callable] = None,
) -> tuple[Callable[[jax.Array, Any, jax.Array], jax.Array], jax.Array, Any]:
    """Stage ``model``'s blocks over ``trial``'s pipe axis.

    ``params`` is a plain ``TransformerLM`` param tree (from
    ``model.init`` / ``create_lm_state``). Returns ``(apply, packed,
    outer)``:

    - ``apply(packed, outer, tokens) -> (B, T, vocab) logits`` — pure
      and differentiable in both param arguments;
    - ``packed`` — the per-stage block params as one pipe-sharded
      array (place with ``parallel.pipeline.stage_params_sharding``);
    - ``outer`` — the embed / final-norm / head params that stay
      resident everywhere.

    ``attention`` overrides the staged blocks' attention (must be
    collective-free; default = the model's own, which must not be a
    ring — pass the dense default or ``make_flash_attention()``).
    """
    from multidisttorch_tpu.parallel.mesh import PIPE_AXIS

    num_stages = int(dict(trial.mesh.shape).get(PIPE_AXIS, 1))
    if num_stages < 2:
        raise ValueError(
            "trial mesh has no pipe axis of extent >= 2; carve one with "
            "setup_groups(..., pipeline_parallel=S)"
        )
    attn = attention if attention is not None else model.attention
    # Ring factories mark their callables carries_collectives=True
    # (shard_map + ppermute hops), which cannot run inside a lax.switch
    # stage branch that only some devices execute. Checked by VALUE,
    # not hasattr: make_flash_attention() sets it False and is staged
    # fine (a plain pallas_call is collective-free).
    if getattr(attn, "carries_collectives", False):
        raise ValueError(
            "staged attention must be collective-free; a ring callable "
            "cannot run inside a pipeline stage (use the dense default "
            "or make_flash_attention())"
        )
    if attn is None:
        from multidisttorch_tpu.ops.ring_attention import (
            dense_attention_reference,
        )

        attn = lambda q, k, v: dense_attention_reference(
            q, k, v, causal=True
        )

    stages = _stage_layers(model.num_layers, num_stages)
    # Stages compute at the model's own dtype (params stay f32 per the
    # pipeline's packing contract; the inter-stage carry is an f32
    # buffer, so a bf16 model pays one cast per stage boundary — the
    # within-stage math is unchanged). model.remat carries over, by
    # the models' own rule: per-block checkpointing composes with the
    # staged schedule.
    block_cls = decoder.block_class(model, Block)
    block_mod = block_cls(
        d_model=model.d_model,
        num_heads=model.num_heads,
        attention=attn,
        dtype=model.dtype,
    )

    def stage_fn(layer_ids):
        def fn(p, x):
            for i in layer_ids:
                x = block_mod.apply({"params": p[f"block_{i}"]}, x)
            return x

        return fn

    stage_fns = [stage_fn(ids) for ids in stages]
    stage_params = [
        {f"block_{i}": params[f"block_{i}"] for i in ids} for ids in stages
    ]
    pp_apply, packed = pipeline_apply_stages(
        trial, stage_fns, stage_params, num_microbatches=num_microbatches
    )

    outer = {
        k: params[k] for k in ("tok_embed", "pos_embed", "ln_out", "head")
    }
    ln = nn.LayerNorm(dtype=model.dtype, param_dtype=jnp.float32)

    def apply(packed_arr, outer_params, tokens):
        _, t = tokens.shape
        if t > model.max_len:
            # Same trace-time contract as TransformerLM.__call__:
            # out-of-range pos-embed gathers clamp silently, not raise.
            raise ValueError(
                f"sequence length {t} exceeds max_len={model.max_len}"
            )
        x = jnp.take(
            outer_params["tok_embed"]["embedding"], tokens, axis=0
        ).astype(model.dtype)
        x = x + jnp.take(
            outer_params["pos_embed"]["embedding"], jnp.arange(t), axis=0
        ).astype(model.dtype)[None, :, :]
        x = pp_apply(packed_arr, x)
        x = ln.apply({"params": outer_params["ln_out"]}, x)
        # head computes in f32, matching TransformerLM's own head Dense
        return (
            x.astype(jnp.float32) @ outer_params["head"]["kernel"]
            + outer_params["head"]["bias"]
        )

    return apply, packed, outer


__all__ = [
    "make_pipelined_lm",
    "stage_params_sharding",
]
