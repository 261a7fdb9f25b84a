"""The jax API the repo uses, in the spelling of the installed jax (0.9).

Import these symbols from here instead of from jax directly, so that the
next upstream move is repaired in this one file:

  * ``shard_map``   — ``jax.shard_map`` (``check_vma``, ``axis_names``);
  * ``make_mesh``   — ``jax.make_mesh`` with every axis ``AxisType.Auto``;
  * ``cost_analysis`` — ``Compiled.cost_analysis()`` as a flat dict;
  * ``enable_x64``  — scoped 64-bit mode (``jax.enable_x64``; the old
                      ``jax.experimental.enable_x64`` is gone);
  * ``optimization_barrier`` — ``jax.lax.optimization_barrier`` with a
                      VJP that barriers the cotangents too.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict."""
    return dict(compiled.cost_analysis())


def enable_x64():
    """Context manager scoping 64-bit mode to a block: arrays made and
    programs traced inside it keep uint64/int64 lanes, while the
    default-dtype behaviour of the model code outside it is untouched."""
    return jax.enable_x64(True)


# -- differentiable optimization_barrier -------------------------------------
@jax.custom_vjp
def optimization_barrier(x):
    """``jax.lax.optimization_barrier`` with an explicit VJP.

    The barrier is semantically the identity, so its VJP is the identity
    on cotangents; we barrier the cotangents too, matching the scheduling
    intent (keep per-iteration gathers inside the backward loop as well).
    """
    return jax.lax.optimization_barrier(x)


def _ob_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _ob_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


optimization_barrier.defvjp(_ob_fwd, _ob_bwd)
