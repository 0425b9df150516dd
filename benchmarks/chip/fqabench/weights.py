"""Seeded weights, drawn on the device, identical bit for bit wherever
they are drawn.

Each weight is keyed by its role (``wq``, ``w_down``, ``ln1/scale``, ...)
and its layer, never by where the program keeps it, so the harness can
fill the program's parameter tree and the reference can draw one layer
at a time and both get the same numbers.  A value is ``(u - 1/2) * s``
with ``u`` a 23-bit uniform on [0, 1) (8 bits for a norm scale) and
``s`` a power of two, so every step is exact and no compiler can round
it differently; the cast to the served dtype is the one rounding.

Every norm scale has outlier channels: every 256th channel, from an
offset drawn for each norm, is scaled by 16, so each linear layer sees a
few input features far larger than the rest.  Trained LLMs have such
features (Dettmers et al., "LLM.int8()", arXiv:2208.07339: a few hidden
dimensions up to 20 times the others), and they are what makes int8
serving lose precision that bf16 keeps; without them the int8 control of
the correctness check would not be told from bf16 serving.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

#: role -> fan-in, as a function of the shape of ONE layer's weight
_FAN_IN = {
    "wq": lambda s: s[0], "wk": lambda s: s[0], "wv": lambda s: s[0],
    "wo": lambda s: s[0] * s[1],
    "w_gate": lambda s: s[0], "w_up": lambda s: s[0], "w_down": lambda s: s[0],
}
#: uniform width of the vocabulary tables: std 2^-4 / sqrt(12) ~ 0.018
_TABLE_WIDTH = 2.0 ** -4
#: norm scales are 1 + (u - 1/2) / 4, in [0.875, 1.125)
_NORM_WIDTH = 2.0 ** -2
#: one norm channel in OUTLIER_EVERY is scaled by OUTLIER_GAIN
OUTLIER_EVERY = 256
OUTLIER_GAIN = 16.0
ROLES = tuple(_FAN_IN) + ("embed", "lm_head", "ln1/scale", "ln2/scale",
                          "ln_f/scale")


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (it may exceed 32 bits)."""
    s = seed % (1 << 62)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, s & 0x7FFFFFFF)
    return jax.random.fold_in(k, s >> 31)


def _width(role: str, shape) -> float:
    if role.endswith("scale"):
        return _NORM_WIDTH
    if role in ("embed", "lm_head"):
        return _TABLE_WIDTH
    fan_in = _FAN_IN[role](shape)
    return 2.0 ** round(math.log2(math.sqrt(12.0 / fan_in)))


def draw(key: jax.Array, role: str, layer: int, shape) -> jax.Array:
    """One layer's weight of ``role`` in float32 (traceable)."""
    if role not in ROLES:
        raise KeyError(f"no weight rule for role {role!r}")
    tag = zlib.crc32(role.encode()) & 0x7FFFFFFF
    k = jax.random.fold_in(jax.random.fold_in(key, tag), layer)
    # a norm scale keeps 8 bits of u, so that 1 + w is exact as well
    nbits = 8 if role.endswith("scale") else 23
    bits = jax.random.bits(k, tuple(shape), jnp.uint32) >> (32 - nbits)
    u = bits.astype(jnp.float32) * jnp.float32(2.0 ** -nbits)
    w = (u - jnp.float32(0.5)) * jnp.float32(_width(role, shape))
    if not role.endswith("scale"):
        return w
    off = jax.random.randint(jax.random.fold_in(k, 1), (), 0, OUTLIER_EVERY)
    big = (jnp.arange(shape[-1]) + off) % OUTLIER_EVERY == 0
    return (w + 1.0) * jnp.where(big, jnp.float32(OUTLIER_GAIN), 1.0)


def role_of(path) -> str:
    """Role of a parameter-tree path: its last key, or the last two for
    a norm scale."""
    names = [str(getattr(p, "key", p)) for p in path]
    return "/".join(names[-2:]) if names[-1] == "scale" else names[-1]
