"""The one keep-or-drop hash of every dropout in the package.

An element is kept where a 32-bit mix of a word that is ITS OWN (its
coordinates, a key) reaches ``floor(rate * 2**32)``: a counter-based
generator in a dozen integer operations an element, where 32 threefry bits
cost some sixty. Being a pure function of the element, the mask is never
stored: a forward pass, its backward and every fusion that consumes the
dropped tensor regenerate the same bits, whatever their tiling or sharding.

Plain ``lax`` on ``uint32`` and no Pallas import (that costs a second of
start-up), so it runs the same inside a kernel body
(``pallas_kernels/flash_attention._dropout_keep_at``, whose masks
``tests/test_flash_dropout.py`` pins), in an XLA fusion
(``tensor_ops.dropout_op``) and on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# the two multipliers of the mixer ("lowbias32"); a compiled program that
# drops by this hash holds both (benchmarks/diag_train_split.py tells a
# drawing fusion by them, tests/test_chip_compile.py the training step)
MUL_A = 0x7FEB352D
MUL_B = 0x846CA68B


def threshold(rate) -> int:
    """The word a mixed hash must reach for its element to be kept: the
    keep probability is ``1 - threshold / 2**32``, within 2.4e-10 of
    ``1 - rate``."""
    return min(int(float(rate) * 4294967296.0), 4294967295)


def mix32(x):
    """xor-shift 16, multiply, xor-shift 15, multiply, xor-shift 16 over a
    ``uint32`` array. ``lax`` operations on the tile, not jnp's: the same
    arithmetic, a quarter of the time to trace, and a kernel that unrolls
    its heads traces this once a head at every start of the program."""

    def tile(c):
        return lax.full_like(x, c)

    x = lax.mul(lax.bitwise_xor(x, lax.shift_right_logical(x, tile(16))),
                tile(MUL_A))
    x = lax.mul(lax.bitwise_xor(x, lax.shift_right_logical(x, tile(15))),
                tile(MUL_B))
    return lax.bitwise_xor(x, lax.shift_right_logical(x, tile(16)))


def keep(x, rate):
    """Whether the element whose hash input is ``x`` survives ``rate``."""
    return lax.ge(mix32(x), lax.full_like(x, threshold(rate)))


def position_words(shape, limit=1 << 32):
    """``(low, high)``: every element's row-major position in an array of
    ``shape`` as ``uint32`` words built from iotas. ``low`` counts within
    the longest run of trailing axes that holds at most ``limit`` elements
    and ``high`` (None where that is the whole array) counts the runs, so
    the pair is unique to the element."""
    low, high, stride, past = jnp.zeros(shape, jnp.uint32), None, 1, False
    for axis in reversed(range(len(shape))):
        if not past and stride * shape[axis] > limit:
            past, stride = True, 1
        if shape[axis] > 1:
            term = lax.broadcasted_iota(jnp.uint32, shape, axis) \
                * jnp.uint32(stride & 0xFFFFFFFF)
            if past:
                high = term if high is None else high + term
            else:
                low = low + term
        stride *= shape[axis]
    return low, high


def keep_mask(key, shape, rate):
    """The keep mask of a WHOLE array of ``shape`` under a two-word ``key``
    (a raw ``uint32[2]`` or a typed PRNG key): ``mix(mix(i ^ k0) ^ k1')``
    over the element's row-major position ``i``, where ``k1'`` is ``k1``
    with ``k0`` folded in (a scalar's work). No two elements of an array
    share a hash input, and two keys that differ in either word give
    unrelated masks: after one round over ``i ^ key`` alone, a key a few
    low bits away drops the same mask with its neighbours exchanged. The
    position is built from iotas, so a partitioned array hashes its GLOBAL
    positions shard by shard, with no collective. Past 2**32 elements the
    position's high word goes into the second key word."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    low, high = position_words(shape)
    k0 = key[-2]
    k1 = lax.broadcast(key[-1] ^ (k0 * jnp.uint32(0x9E3779B9)), shape)
    if high is not None:
        k1 = lax.bitwise_xor(k1, high * jnp.uint32(0xC2B2AE35))
    x = mix32(lax.bitwise_xor(low, lax.broadcast(k0, shape)))
    return keep(lax.bitwise_xor(x, k1), rate)
