"""The yardstick: chip peaks, the work a dense GQA decoder needs, and
the whole step's share of the peak from any program's work counts.

Everything here is computed from shapes and token counts, never from the
program under test, so a change to the program cannot move it.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Protocol

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect).
_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, TPU v5e",
}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; an unknown device is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


class Counts(Protocol):
    """The work of a program's steps, from shapes and token counts: what
    a program module's ``counts(conf)`` returns, and what
    :func:`traced_mfu` and the per-layer readers call."""

    def prefill_flops(self, prompt_len: int) -> float:
        """Model FLOPs to prefill one prompt of real (unpadded) length."""

    def decode_flops(self, keys: int) -> float:
        """Model FLOPs of one decoded token that attends to ``keys``
        positions (its own included)."""

    def decode_step_bytes(self, keys: Iterable[int]) -> float:
        """HBM bytes one decode step needs, with one sequence for each
        entry of ``keys`` (the positions it attends)."""


@dataclasses.dataclass(frozen=True)
class Dims:
    """The counts of program ``dense_gqa``: shapes of a dense decoder
    with grouped-query attention and a SwiGLU MLP, as its configuration
    file states them."""

    d_model: int
    n_q: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d_model=c["hidden_size"], n_q=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   layers=c["num_hidden_layers"])

    # ------------------------------------------------------------ params
    @property
    def layer_matmul_params(self) -> int:
        d, dh = self.d_model, self.head_dim
        attn = d * (self.n_q + 2 * self.n_kv) * dh + self.n_q * dh * d
        return attn + 3 * d * self.d_ff

    @property
    def nonembedding_params(self) -> int:
        """Matmul weights of every layer (norm scales left out)."""
        return self.layers * self.layer_matmul_params

    @property
    def norm_params(self) -> int:
        return (2 * self.layers + 1) * self.d_model

    @property
    def table_params(self) -> int:
        """One vocab x d_model table (embedding or untied LM head)."""
        return self.vocab * self.d_model

    # ------------------------------------------------------------- work
    def prefill_flops(self, prompt_len: int) -> float:
        """Model FLOPs to prefill one prompt of real (unpadded) length:
        2 per weight per token, causal attention at 4·layers·n_q·head_dim
        per query-key pair, and the LM head once, for the last token."""
        t = prompt_len
        return (2.0 * self.nonembedding_params * t
                + 4.0 * self.layers * self.n_q * self.head_dim
                * t * (t + 1) / 2
                + 2.0 * self.table_params)

    def decode_flops(self, keys: int) -> float:
        """Model FLOPs of one decoded token that attends to ``keys``
        positions (its own included)."""
        return (2.0 * self.nonembedding_params
                + 4.0 * self.layers * self.n_q * self.head_dim * keys
                + 2.0 * self.table_params)

    def kv_bytes_per_token(self, cache_bytes: int = 2) -> int:
        """K and V of one position across all layers."""
        return self.layers * 2 * self.n_kv * self.head_dim * cache_bytes

    def weight_bytes(self, weight_bytes_each: int = 2) -> int:
        """Weights a decode step reads once: every layer, the final norm
        and the LM head (the embedding contributes only its gathered
        rows)."""
        return weight_bytes_each * (self.nonembedding_params
                                    + self.norm_params + self.table_params)

    def decode_step_bytes(self, keys: Iterable[int],
                          weight_bytes_each: int = 2,
                          cache_bytes: int = 2) -> float:
        """HBM bytes one decode step needs: the weights once, one
        embedding row per sequence, and the K/V of each sequence's live
        context."""
        keys = list(keys)
        return (self.weight_bytes(weight_bytes_each)
                + len(keys) * self.d_model * weight_bytes_each
                + sum(keys) * self.kv_bytes_per_token(cache_bytes))


# the dense counts, as functions of a Dims
prefill_flops = Dims.prefill_flops
decode_flops = Dims.decode_flops
kv_bytes_per_token = Dims.kv_bytes_per_token
weight_bytes = Dims.weight_bytes
decode_step_bytes = Dims.decode_step_bytes


def step_flops(counts: Counts, prefill_lens: Iterable[int],
               decode_keys: Iterable[int]) -> float:
    """Model FLOPs of one engine step: the prompts it prefilled and the
    tokens it decoded (each given by the keys it attended)."""
    return (sum(counts.prefill_flops(t) for t in prefill_lens)
            + sum(counts.decode_flops(k) for k in decode_keys))


def traced_mfu(run) -> Optional[float]:
    """Model FLOPs of the steps in a run's traced window over the window's
    length times the chip's bf16 peak, in %: the whole step's share of
    the peak, whichever kernels it runs; None with nothing traced."""
    steps = run.traced_steps()
    if not steps:
        return None
    flops = sum(step_flops(run.counts, s.prefill_lens, s.decode_keys)
                for s in steps)
    window = run.window_s - run.traced_from
    return 100.0 * flops / (window * run.peaks["bf16_flops_per_s"])
