"""Seeded weights, made on the device in the type they are served in.

Every value comes from ``jax.random.uniform`` (integer bit operations, a
subtraction and one scaling), so the same key gives bit-identical
weights in any program that calls these functions: the program's
weights and the reference's are made by the same jitted call and never
pass through each other. A layer's weights depend only on (seed, layer),
so the reference can make them again one layer at a time after the
program's state is freed.

The format of the linears (dense, SLaB N:M, ...) is a module of its own
under ``chipbench/formats``, named by the configuration's
``format.kind``.
"""
from __future__ import annotations

import functools
import importlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TAG_EMBED, TAG_HEAD, TAG_NORMS, TAG_LAYER = 1, 2, 3, 1000


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (it may need more than 32
    bits): the seed is hashed to one 32-bit word first."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def sub_key(key: jax.Array, tag: int) -> jax.Array:
    return jax.random.fold_in(key, tag)


def uniform(key, shape, lo: float, hi: float, dtype) -> jax.Array:
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


@functools.partial(jax.jit, static_argnames=("vocab", "d"))
def embed(key, vocab: int, d: int) -> jax.Array:
    """(vocab, d) bf16 token embedding, uniform in [-1, 1)."""
    return uniform(sub_key(key, TAG_EMBED), (vocab, d), -1.0, 1.0,
                   jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("vocab", "d"))
def head(key, vocab: int, d: int) -> jax.Array:
    """(d, vocab) bf16 output head, variance 1 / d."""
    a = (3.0 / d) ** 0.5
    return uniform(sub_key(key, TAG_HEAD), (d, vocab), -a, a, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("n_layers", "d"))
def norms(key, n_layers: int, d: int) -> Tuple[jax.Array, jax.Array,
                                                 jax.Array]:
    """RMSNorm scales in f32 around 1: (attn (L, d), mlp (L, d),
    final (d,))."""
    k = sub_key(key, TAG_NORMS)
    s = uniform(k, (2 * n_layers + 1, d), 0.75, 1.25, jnp.float32)
    return s[:n_layers], s[n_layers:2 * n_layers], s[-1]


def layer_key(key: jax.Array, layer: int) -> jax.Array:
    return sub_key(key, TAG_LAYER + layer)


def format_module(kind: str):
    """The module of ``chipbench/formats`` that makes and packs linears
    of this format."""
    return importlib.import_module(f"chipbench.formats.{kind}")


Shapes = Dict[str, Tuple[int, int]]
