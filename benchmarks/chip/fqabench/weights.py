"""Seeded weights, drawn on the device, identical bit for bit wherever
they are drawn.

Each weight is keyed by its role (``wq``, ``w_down``, ``ln1/scale``, ...)
and its layer, never by where the program keeps it, so the harness can
fill the program's parameter tree and the reference can draw one layer
at a time and both get the same numbers.  A value is ``(u - 1/2) * s``
(plus 1 for a norm scale) with ``u`` a 23-bit uniform on [0, 1) (8 bits
for a norm scale) and ``s`` a power of two, so every step is exact and
no compiler can round it differently; the cast to the served dtype is
the one rounding.

``RULES`` holds the dense decoder's roles.  A program module
(``programs/<name>.py``) that has other roles (an SSM's projections, a
router, experts) names their rules in its ``ROLES``, which program and
reference alike pass to :func:`draw`: it adds roles, and may not redraw
a dense one.

Every norm scale has outlier channels: every 256th channel, from an
offset drawn for each norm, is scaled by 16, so each linear layer sees a
few input features far larger than the rest.  Trained LLMs have such
features (Dettmers et al., "LLM.int8()", arXiv:2208.07339: a few hidden
dimensions up to 20 times the others), and they are what makes int8
serving lose precision that bf16 keeps; without them the int8 control of
the correctness check would not be told from bf16 serving.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Callable, Mapping, Optional

import jax
import jax.numpy as jnp

#: uniform width of the vocabulary tables: std 2^-4 / sqrt(12) ~ 0.018
_TABLE_WIDTH = 2.0 ** -4
#: norm scales are 1 + (u - 1/2) / 4, in [0.875, 1.125)
_NORM_WIDTH = 2.0 ** -2
#: one norm channel in OUTLIER_EVERY is scaled by OUTLIER_GAIN
OUTLIER_EVERY = 256
OUTLIER_GAIN = 16.0


@dataclasses.dataclass(frozen=True)
class Rule:
    """How one role's weight is drawn: ``(u - 1/2) * width(shape) +
    center`` with ``u`` a uniform of ``nbits`` bits; with ``outliers``,
    one channel of the last axis in OUTLIER_EVERY (from a seeded offset)
    is then multiplied by OUTLIER_GAIN.  ``width`` takes the shape of ONE
    layer's weight and returns a power of two, so every step is exact."""

    width: Callable[[tuple], float]
    center: float = 0.0
    nbits: int = 23
    outliers: bool = False


def fan_in(count: Callable[[tuple], int]) -> Rule:
    """A matmul weight whose fan-in is ``count(shape)``: the power of two
    nearest a unit-variance output, ``sqrt(12 / fan_in)``."""
    return Rule(lambda s: 2.0 ** round(math.log2(math.sqrt(12.0 / count(s)))))


def uniform(width: float, center: float = 0.0) -> Rule:
    """A weight uniform on ``center + [-width/2, width/2)`` whatever its
    shape (``width`` a power of two)."""
    return Rule(lambda s: width, center)


#: a norm scale keeps 8 bits of u, so that 1 + w is exact as well
NORM = Rule(lambda s: _NORM_WIDTH, center=1.0, nbits=8, outliers=True)
#: the dense decoder's roles; a program module adds its own in ``ROLES``
RULES = {
    "wq": fan_in(lambda s: s[0]), "wk": fan_in(lambda s: s[0]),
    "wv": fan_in(lambda s: s[0]), "wo": fan_in(lambda s: s[0] * s[1]),
    "w_gate": fan_in(lambda s: s[0]), "w_up": fan_in(lambda s: s[0]),
    "w_down": fan_in(lambda s: s[0]),
    "embed": uniform(_TABLE_WIDTH), "lm_head": uniform(_TABLE_WIDTH),
    "ln1/scale": NORM, "ln2/scale": NORM, "ln_f/scale": NORM,
}


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (it may exceed 32 bits)."""
    s = seed % (1 << 62)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, s & 0x7FFFFFFF)
    return jax.random.fold_in(k, s >> 31)


def check_extra(extra: Mapping[str, Rule]) -> None:
    """Refuse extra rules that would redraw a dense role."""
    clash = sorted(set(extra) & set(RULES))
    if clash:
        raise ValueError(f"extra weight rules may not redraw the dense "
                         f"roles {clash}")


def draw(key: jax.Array, role: str, layer: int, shape,
         extra: Optional[Mapping[str, Rule]] = None) -> jax.Array:
    """One layer's weight of ``role`` in float32 (traceable), by the
    dense rules or ``extra`` (a program module's ``ROLES``)."""
    rule = RULES.get(role) or (extra or {}).get(role)
    if rule is None:
        raise KeyError(f"no weight rule for role {role!r}")
    tag = zlib.crc32(role.encode()) & 0x7FFFFFFF
    k = jax.random.fold_in(jax.random.fold_in(key, tag), layer)
    bits = jax.random.bits(k, tuple(shape), jnp.uint32) >> (32 - rule.nbits)
    u = bits.astype(jnp.float32) * jnp.float32(2.0 ** -rule.nbits)
    w = (u - jnp.float32(0.5)) * jnp.float32(rule.width(tuple(shape)))
    if rule.center:
        w = w + jnp.float32(rule.center)
    if not rule.outliers:
        return w
    off = jax.random.randint(jax.random.fold_in(k, 1), (), 0, OUTLIER_EVERY)
    big = (jnp.arange(shape[-1]) + off) % OUTLIER_EVERY == 0
    return w * jnp.where(big, jnp.float32(OUTLIER_GAIN), 1.0)


def role_of(path) -> str:
    """Role of a parameter-tree path: its last key, or the last two for
    a norm scale."""
    names = [str(getattr(p, "key", p)) for p in path]
    return "/".join(names[-2:]) if names[-1] == "scale" else names[-1]
