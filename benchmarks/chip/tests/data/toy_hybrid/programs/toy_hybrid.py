"""A small hybrid decoder (hymba's layout: attention heads and Mamba SSM
heads side by side in every layer, global attention on the layers in
``global_attn_idx`` and a sliding window on the rest), built by the
program from a configuration file that names ``"program":
"toy_hybrid"``.  A test adds it to a copy of the benchmark as new files
only, to show that a family other than the dense decoder joins that way.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterable, Optional, Tuple

from fqabench import model, weights

#: the SSM's roles, beyond the dense decoder's
ROLES = {
    "w_in": weights.fan_in(lambda s: s[0]),
    "conv_w": weights.fan_in(lambda s: s[0]),
    "conv_b": weights.uniform(2.0 ** -4),
    "w_x": weights.fan_in(lambda s: s[0]),
    "w_dt": weights.fan_in(lambda s: s[0]),
    # softplus(dt_bias) in about [0.007, 0.05], a step as Mamba draws it
    "dt_bias": weights.uniform(2.0, center=-4.0),
    # A = exp(a_log) in [1, 7.4), as Mamba's A in 1..d_state
    "a_log": weights.uniform(2.0, center=1.0),
    "d_skip": weights.uniform(2.0 ** -1, center=1.0),
    "w_out": weights.fan_in(lambda s: s[0]),
}


def _windows(conf: dict) -> Tuple[Optional[int], ...]:
    glob = set(conf["global_attn_idx"])
    return tuple(None if i in glob else conf["sliding_window"]
                 for i in range(conf["num_hidden_layers"]))


def program_cfg(conf: dict):
    """``repro`` ModelCfg of a hybrid decoder as ``conf`` states it."""
    from repro.configs import get_config
    from repro.models import StageCfg

    base = get_config(conf["program_arch"])
    stages = tuple(StageCfg("hyb", len(list(run)), window=w)
                   for w, run in itertools.groupby(_windows(conf)))
    return base.replace(
        arch=conf["name"],
        d_model=conf["hidden_size"], n_q=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        stages=stages,
        ssm_inner=conf["mamba_expand"] * conf["hidden_size"],
        ssm_state=conf["mamba_d_state"], ssm_conv=conf["mamba_d_conv"],
        ssm_dt_rank=conf["mamba_dt_rank"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        act_impl=conf["act_impl"],
        param_dtype=conf["torch_dtype"],
        compute_dtype=conf["torch_dtype"])


def program_params(cfg, seed: int):
    return model.draw_params(cfg, seed, ROLES)


@dataclasses.dataclass(frozen=True)
class Counts:
    """Work of the hybrid's steps: the dense decoder's matmuls, attention
    over at most a layer's window of keys, and the SSM's projections,
    depthwise conv and state update (6 operations per state element and
    token: the decay, the drive, the update and the read-out)."""

    d_model: int
    n_q: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    windows: Tuple[Optional[int], ...]
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int

    @property
    def layer_matmul_params(self) -> int:
        d, dh, di = self.d_model, self.head_dim, self.d_inner
        attn = d * (self.n_q + 2 * self.n_kv) * dh + self.n_q * dh * d
        ssm = (d * 2 * di + di * (self.dt_rank + 2 * self.d_state)
               + self.dt_rank * di + di * d)
        return attn + ssm + 3 * d * self.d_ff

    def _token_flops(self) -> float:
        """FLOPs of one token outside attention's query-key pairs."""
        layers = len(self.windows)
        per_layer = (2.0 * self.layer_matmul_params
                     + 2.0 * self.d_conv * self.d_inner
                     + 6.0 * self.d_inner * self.d_state)
        return layers * per_layer

    def _pair_flops(self) -> float:
        return 4.0 * self.n_q * self.head_dim

    def prefill_flops(self, prompt_len: int) -> float:
        t = prompt_len
        pairs = sum(t * (t + 1) / 2 if w is None or w >= t
                    else w * (w + 1) / 2 + (t - w) * w
                    for w in self.windows)
        return (self._token_flops() * t + self._pair_flops() * pairs
                + 2.0 * self.vocab * self.d_model)

    def decode_flops(self, keys: int) -> float:
        seen = sum(keys if w is None else min(keys, w) for w in self.windows)
        return (self._token_flops() + self._pair_flops() * seen
                + 2.0 * self.vocab * self.d_model)

    def decode_step_bytes(self, keys: Iterable[int]) -> float:
        """Weights once (bf16), one embedding row per sequence, the K/V
        each sequence's layers keep, and its SSM state read and written
        (float32 ``h``, bf16 conv tail)."""
        keys = list(keys)
        layers = len(self.windows)
        weight = 2 * (layers * self.layer_matmul_params
                      + (2 * layers + 1) * self.d_model
                      + self.vocab * self.d_model)
        kv = sum(2 * self.n_kv * self.head_dim * 2
                 * (k if w is None else min(k, w))
                 for k in keys for w in self.windows)
        state = layers * 2 * (4 * self.d_inner * self.d_state
                              + 2 * (self.d_conv - 1) * self.d_inner)
        return (weight + len(keys) * 2 * self.d_model + kv
                + len(keys) * state)


def counts(conf: dict) -> Counts:
    return Counts(d_model=conf["hidden_size"],
                  n_q=conf["num_attention_heads"],
                  n_kv=conf["num_key_value_heads"],
                  head_dim=conf["head_dim"],
                  d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                  windows=_windows(conf),
                  d_inner=conf["mamba_expand"] * conf["hidden_size"],
                  d_state=conf["mamba_d_state"], d_conv=conf["mamba_d_conv"],
                  dt_rank=conf["mamba_dt_rank"])
