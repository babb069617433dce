"""The controls: what each cell's comparison must refuse.

For the job-model cells the control is the program's own evaluator with its
base configuration in bfloat16, the precision below the float32 the device
path runs in: the evaluator casts every override to its base's dtype, so the
same chunked programs then compute every equation in bfloat16.  For the
planner the control is the plain reference computed in bfloat16, put in the
place of the device's costs (``planner_grid.Load(control_dtype=...)``)."""

from __future__ import annotations

import copy

__all__ = ["bf16_evaluator", "control_kw"]


def bf16_evaluator(inner):
    """A copy of a chunked evaluator whose base configuration (and so every
    cast override) is bfloat16; the original is left as it was."""
    import jax.numpy as jnp

    ev = copy.copy(inner)
    ev.base_cfg = {k: jnp.asarray(v, dtype=jnp.bfloat16) for k, v in inner.base_cfg.items()}
    return ev


def control_kw(load: str) -> dict:
    """Arguments of the load that turn a run of it into its control."""
    if load == "planner_grid":
        import ml_dtypes

        return {"control_dtype": ml_dtypes.bfloat16}
    return {"plant": bf16_evaluator}
