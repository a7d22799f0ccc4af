"""Dense bf16 linears, stored (d_in, d_out) as the program multiplies
them: ``x @ w``."""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from chipbench import weights


@functools.partial(jax.jit, static_argnames=("shapes",))
def _make(key, shapes):
    out = {}
    for i, (path, (d_in, d_out)) in enumerate(shapes):
        a = (3.0 / d_in) ** 0.5
        out[path] = weights.uniform(jax.random.fold_in(key, i),
                                    (d_in, d_out), -a, a, jnp.bfloat16)
    return out


def make_layer(key, shapes: weights.Shapes, fmt: dict) -> Dict:
    """Each linear uniform with variance 1 / d_in, in bf16."""
    return _make(key, tuple(sorted(shapes.items())))


def program_linears(make: Callable[[int], Dict], n_layers: int, fmt: dict,
                    dtype=jnp.bfloat16) -> Dict:
    """Stacked (L, d_in, d_out) leaves, written layer by layer into
    buffers the update donates, so no second copy is ever held."""
    put = jax.jit(lambda buf, w, l: jax.lax.dynamic_update_index_in_dim(
        buf, w.astype(buf.dtype), l, 0), donate_argnums=0)
    stacked = None
    for l in range(n_layers):
        layer = make(l)
        if stacked is None:
            stacked = {p: jnp.zeros((n_layers,) + w.shape, dtype)
                       for p, w in layer.items()}
        for p in stacked:
            stacked[p] = put(stacked[p], layer[p], l)
        del layer
    return stacked


def dense_equivalent(part) -> jax.Array:
    return part.astype(jnp.float32)
