"""Device twin of utils/hashing.py — MurmurHash3 fmix32 over uint32 lanes.

Bit-identical to the numpy version (tests/parity/test_encoder_parity.py):
uint32 multiply/xor/shift wrap the same way in XLA as in numpy, and JAX x64
stays disabled so everything is 32-bit on TPU (VPU-friendly integer ops).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy scalars, not jnp: a jnp constant at import would initialize the
# backend (and claim the chip) in every process that imports this module
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """MurmurHash3 fmix32 finalizer over uint32 arrays."""
    h = x.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def hash_u32(key: jnp.ndarray, seed: jnp.ndarray | int) -> jnp.ndarray:
    """hash(seed, key) -> uint32; key any integer array (cast mod 2^32)."""
    k = key.astype(jnp.uint32)
    return fmix32(k * _GOLDEN + jnp.asarray(seed, jnp.uint32))


def hash_bits(keys: jnp.ndarray, seed: jnp.ndarray | int, n: int) -> jnp.ndarray:
    """Map integer keys to bit indices in [0, n). RDSE device path."""
    return (hash_u32(keys, seed) % jnp.uint32(n)).astype(jnp.int32)
