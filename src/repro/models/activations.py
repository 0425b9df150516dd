"""Activation implementation selection: exact jnp vs FQA PPA tables.

This is where the paper's artifact becomes a first-class framework feature.
An :class:`ActBundle` holds the callables every model block needs — silu,
gelu, sigmoid, tanh, softplus, exp-decay and softmax — each backed either
by the exact float op or by a compiled :class:`PPATable` running the
fixed-point FQA datapath (with straight-through gradients for training).

``make_acts(impl=...)``:
  "exact"  — jnp ops (the float baseline every PPA run is compared to)
  "ppa"    — FQA tables at the given deployment precision (default: the
             paper's 16-bit-output FQA-O2 configuration, wide-domain
             variants for the model-range functions)
  "ppa8"   — the 8-bit FQA-S4-O1 deployment point (aggressive, for
             accuracy-degradation studies)

Every callable of a bundle runs under ``jax.named_scope("act.<field>")``
(``act.silu``, ..., ``act.softmax``) whichever implementation backs it,
so the ops it lowers to carry that scope in their HLO ``op_name`` and a
profiler trace can sum their device time by activation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.compiler import compile_or_load
from repro.core import FWLConfig, PPAScheme
from repro.kernels.ops import (TableConsts, pack_table, ppa_act,
                               ppa_gate_act, ppa_softmax)

__all__ = ["ActBundle", "make_acts", "ppa_table_jobs"]

Act = Callable[[jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class ActBundle:
    impl: str
    sigmoid: Act
    tanh: Act
    gelu: Act          # full gelu(x) = x * Phi(x)
    silu: Act          # full silu(x) = x * sigmoid(x)
    softplus: Act
    exp_decay: Act     # e^-x for x >= 0 (SSM/RWKV decays)
    softmax: Callable  # (x, axis=-1, where=None)

    def gate(self, kind: str) -> Act:
        return {"silu": self.silu, "gelu": self.gelu,
                "sigmoid": self.sigmoid, "tanh": self.tanh}[kind]


def _exact_bundle() -> ActBundle:
    def softmax(x, axis=-1, where=None):
        if where is not None:
            x = jnp.where(where, x, jnp.finfo(x.dtype).min)
        return jax.nn.softmax(x, axis=axis)
    return ActBundle(
        impl="exact",
        sigmoid=jax.nn.sigmoid, tanh=jnp.tanh, gelu=jax.nn.gelu,
        silu=jax.nn.silu, softplus=jax.nn.softplus,
        exp_decay=lambda x: jnp.exp(-x), softmax=softmax)


# deployment FWL points (paper Table VI/VII conclusions):
#   16-bit: FQA-O2  W_i=8 W_a=(8,16) W_o=(16,16) W_b=16
#   8-bit:  FQA-S4-O1 (multiplierless, hamming<=4)
_CFG16 = FWLConfig(w_in=8, w_out=16, w_a=(8, 16), w_o=(16, 16), w_b=16)
_CFG8 = FWLConfig(w_in=8, w_out=8, w_a=(8,), w_o=(8,), w_b=8)
_SCHEME16 = PPAScheme(order=2, quantizer="fqa")
_SCHEME8 = PPAScheme(order=1, m_shifters=4, quantizer="fqa")


#: the NAF zoo a served model touches: gates + softmax exp2 + SSM/RWKV
#: decays — one table each per deployment bit-width.
_PPA_NAFS = ("sigmoid_wide", "tanh_wide", "gelu_inner", "softplus",
             "exp_neg", "exp2_frac")


def ppa_table_jobs(impl: str):
    """The (naf, FWLConfig, PPAScheme) set an ``impl`` deployment needs.

    This is the tenant warm-up contract: resolving each returned triple
    through ``compile_or_load`` (and pinning it) guarantees the serving
    hot path never compiles — or evicts — a table mid-request.  Empty for
    the exact float impl.
    """
    if impl == "exact":
        return []
    if impl in ("ppa", "ppa16"):
        cfg, scheme = _CFG16, _SCHEME16
    elif impl == "ppa8":
        cfg, scheme = _CFG8, _SCHEME8
    else:
        raise ValueError(f"unknown activation impl {impl!r}")
    return [(naf, cfg, scheme) for naf in _PPA_NAFS]


@functools.lru_cache(maxsize=None)
def _tc(naf: str, bits: int, store) -> TableConsts:
    cfg, scheme = (_CFG16, _SCHEME16) if bits == 16 else (_CFG8, _SCHEME8)
    # wide-domain tables keep the fractional in-grid at w_in bits; the
    # integer span of the interval only widens the comparator range.
    # Resolution goes through the table store (memory -> disk -> compile):
    # model construction never compiles a table another consumer already
    # has, and a served model's tables are plain JSON artifacts on disk.
    # ``store`` is a concrete TableStore (identity-hashed cache key) —
    # make_acts resolves the process default before the cache, so bundles
    # are cached per concrete store, never per "whatever default was".
    return pack_table(compile_or_load(naf, cfg, scheme, store=store))


def _ppa_bundle(bits: int, backend: str, store=None) -> ActBundle:
    sig = _tc("sigmoid_wide", bits, store)
    tnh = _tc("tanh_wide", bits, store)
    phi = _tc("gelu_inner", bits, store)
    sp = _tc("softplus", bits, store)
    en = _tc("exp_neg", bits, store)
    e2 = _tc("exp2_frac", bits, store)

    def sigmoid(x):
        return ppa_act(sig, x, backend)

    def tanh(x):
        return ppa_act(tnh, x, backend)

    def gelu(x):
        # gated op: on the fused backend the x * Phi(x) multiply happens
        # inside the kernel; identical float32 math on every other backend
        return ppa_gate_act(phi, x, backend)

    def silu(x):
        return ppa_gate_act(sig, x, backend)

    def softplus(x):
        return ppa_act(sp, x, backend)

    def exp_decay(x):
        return ppa_act(en, x, backend)

    def softmax(x, axis=-1, where=None):
        return ppa_softmax(e2, x, axis=axis, where=where, backend=backend)

    return ActBundle(impl=f"ppa{bits}", sigmoid=sigmoid, tanh=tanh,
                     gelu=gelu, silu=silu, softplus=softplus,
                     exp_decay=exp_decay, softmax=softmax)


def _scoped(bundle: ActBundle) -> ActBundle:
    """``bundle`` with each callable under ``jax.named_scope("act.<field>")``:
    op metadata only, the program is unchanged."""
    def wrap(name: str, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return dataclasses.replace(bundle, **{
        f.name: wrap(f"act.{f.name}", getattr(bundle, f.name))
        for f in dataclasses.fields(bundle) if f.name != "impl"})


@functools.lru_cache(maxsize=None)
def _cached_bundle(impl: str, backend: str, store) -> ActBundle:
    if impl == "exact":
        return _scoped(_exact_bundle())
    if impl in ("ppa", "ppa16"):
        return _scoped(_ppa_bundle(16, backend, store))
    if impl == "ppa8":
        return _scoped(_ppa_bundle(8, backend, store))
    raise ValueError(f"unknown activation impl {impl!r}")


def make_acts(impl: str = "exact", backend: str = "ref",
              store=None) -> ActBundle:
    """``store``: optional :class:`repro.compiler.TableStore` the PPA
    tables resolve through.  None resolves the *current* process default
    at every call (so ``set_default_store`` takes effect for later
    bundles); the concrete store is part of the bundle cache key, so
    consumers pinning different stores get distinct bundles."""
    if store is None and impl != "exact":
        from repro.compiler import default_store
        store = default_store()
    return _cached_bundle(impl, backend, store)
