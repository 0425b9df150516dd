"""Plain float32 reference of a dense decoder with grouped-query
attention, RoPE, RMSNorm and a SwiGLU MLP (internlm2, mistral-nemo).

It imports nothing of the program.  Its weights are drawn from the seed
by :mod:`fqabench.weights`, one layer at a time, in the served dtype and
then widened to float32, by the rules of the program module that the
configuration names (``programs/<program>.py``), so both draw the same
bits.  Every matmul runs at ``highest`` precision, and
the nonlinearities are the exact functions (exp in the softmax, sigmoid
in the SwiGLU gate): the program's 16-bit FQA tables approximate these,
and their error is part of what the comparison sees.

``mode="int8"`` is the control: every linear layer and the LM head take
their inputs and weights through symmetric int8 (W8A8: one scale per
token of the input, one per output channel of the weight, each putting
the largest magnitude on 127, rounded to nearest) and accumulate in
float32, the usual int8 serving recipe.  The rounding is done in float32
arithmetic, so it needs no int8 matmul on the device.
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fqabench import harness, weights

BENCH_DIR = Path(__file__).resolve().parents[1]
HI = jax.lax.Precision.HIGHEST
#: served tokens whose logits go through the LM head at once
HEAD_ROWS = 256


def _int8(x, axes):
    """Round ``x`` to symmetric int8 with one scale per slice over
    ``axes`` (the contracted axes), and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s


def _mm(spec, x, w, mode):
    """einsum ``spec`` of activation ``x`` and weight ``w``."""
    if mode == "int8":
        ins, out = spec.split("->")
        a, b = ins.split(",")
        red = (set(a) & set(b)) - set(out)
        x = _int8(x, tuple(i for i, c in enumerate(a) if c in red))
        w = _int8(w, tuple(i for i, c in enumerate(b) if c in red))
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE; x (T, H, D), pos (T,)."""
    d = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64)
                                         / d)), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _shapes(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    hq, hk, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    return {"wq": (d, hq, dh), "wk": (d, hk, dh), "wv": (d, hk, dh),
            "wo": (hq, dh, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d), "ln1/scale": (d,), "ln2/scale": (d,)}


def _served(w, dtype):
    """The weight as the model serves it, widened to float32."""
    return w.astype(dtype).astype(jnp.float32)


class Reference:
    """One configuration's reference, with its weights from one seed."""

    def __init__(self, conf: dict, seed: int):
        self.c = conf
        self.key = weights.base_key(seed)
        self.rules = harness.weight_rules(BENCH_DIR, conf)
        self.dtype = jnp.dtype(conf["torch_dtype"])
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        self.shapes = _shapes(conf)
        self.table_shape = (conf["vocab_size"], conf["hidden_size"])

    # ------------------------------------------------------------ weights
    @functools.partial(jax.jit, static_argnums=0)
    def _layer_weights(self, key, layer):
        return {r: _served(weights.draw(key, r, layer, s, self.rules),
                           self.dtype)
                for r, s in self.shapes.items()}

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _table(self, key, role):
        return _served(weights.draw(key, role, 0, self.table_shape,
                                    self.rules), self.dtype)

    # -------------------------------------------------------------- layers
    def _attend(self, q, k, v):
        """Causal GQA attention of one sequence: q (T, Hq, D), k/v (T, Hk, D)."""
        t, hq, dh = q.shape
        g = hq // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / np.sqrt(dh)
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hts,shd->thd", p, v, precision=HI)

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _layer(self, h, w, mode):
        pos = jnp.arange(h.shape[1])
        x = _rmsnorm(h, w["ln1/scale"], self.eps)
        q = _mm("btd,dhe->bthe", x, w["wq"], mode)
        k = _mm("btd,dhe->bthe", x, w["wk"], mode)
        v = _mm("btd,dhe->bthe", x, w["wv"], mode)
        rope = jax.vmap(lambda a: _rope(a, pos, self.theta))
        o = jax.lax.map(lambda qkv: self._attend(*qkv), (rope(q), rope(k), v))
        h = h + _mm("bthe,hed->btd", o, w["wo"], mode)
        x = _rmsnorm(h, w["ln2/scale"], self.eps)
        gate = _mm("btd,df->btf", x, w["w_gate"], mode)
        up = _mm("btd,df->btf", x, w["w_up"], mode)
        return h + _mm("btf,fd->btd", jax.nn.silu(gate) * up, w["w_down"],
                       mode)

    @functools.partial(jax.jit, static_argnums=0)
    def _final(self, h, key):
        w = _served(weights.draw(key, "ln_f/scale", 0,
                                 (self.c["hidden_size"],), self.rules),
                    self.dtype)
        return _rmsnorm(h, w, self.eps)

    def hidden(self, tokens: np.ndarray, mode: str = "float32") -> jax.Array:
        """Final normed hidden states (B, T, d) of ``tokens`` (B, T)."""
        emb = self._table(self.key, "embed")
        h = jnp.take(emb, jnp.asarray(tokens), axis=0)
        del emb
        for layer in range(self.c["num_hidden_layers"]):
            h = self._layer(h, self._layer_weights(self.key, layer), mode)
        return self._final(h, self.key)

    # ---------------------------------------------------------------- head
    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _head_rows(self, hs, head, mode):
        return _mm("nd,vd->nv", hs, head, mode)

    def logits_rows(self, hs: jax.Array, mode: str = "float32"):
        """Yield the LM head's logits of ``hs`` (N, d), HEAD_ROWS at a time,
        as (start, logits) with logits (HEAD_ROWS, V)."""
        head = self._table(self.key, "lm_head")
        n = hs.shape[0]
        pad = -n % HEAD_ROWS
        hs = jnp.pad(hs, ((0, pad), (0, 0)))
        for i in range(0, n, HEAD_ROWS):
            yield i, self._head_rows(hs[i:i + HEAD_ROWS], head, mode)


def batch_tokens(seqs: Sequence[Tuple], rows: int, length: int):
    """Prompt + served tokens (the last served one is never an input) of
    each sequence, right-padded into a (rows, length) batch, and for each
    served token the (row, position) whose logits chose it."""
    tokens = np.zeros((rows, length), np.int32)
    where: List[Tuple[int, int]] = []
    for i, (prompt, served, *_) in enumerate(seqs):
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        where += [(i, len(prompt) - 1 + j) for j in range(len(served))]
    return tokens, where


def _rows(x, i):
    """Rows ``i`` .. ``i + HEAD_ROWS`` of ``x``, zero-padded to HEAD_ROWS."""
    part = x[i:i + HEAD_ROWS]
    return jnp.asarray(np.pad(part, (0, HEAD_ROWS - len(part))))


def compare(conf: dict, seed: int, seqs, rows: int, length: int,
            control: bool = False) -> dict:
    """Each served token of ``seqs`` (prompt, served tokens, the server's
    best logit at each) against the float32 reference at its position:

    * ``err``: how far the server's best logit lies from the reference's
      logit of the token it served;
    * ``gap``: how far that reference logit lies below the reference's
      best.

    With ``control`` the int8 reference is put in the server's place: at
    each position of the same prompts and tokens it serves the token it
    ranks first, with its own best logit."""
    ref = Reference(conf, seed)
    tokens, where = batch_tokens(seqs, rows, length)
    n = len(where)
    chosen = np.asarray([t for s in seqs for t in s[1]], np.int32)
    best = np.concatenate([np.asarray(s[2], np.float32) for s in seqs])
    idx = tuple(np.asarray(a) for a in zip(*where))
    hs = ref.hidden(tokens)[idx]
    if control:
        hs8 = ref.hidden(tokens, "int8")[idx]
        picked = [(np.asarray(jnp.argmax(lg, -1)), np.asarray(jnp.max(lg, -1)))
                  for _, lg in ref.logits_rows(hs8, "int8")]
        chosen = np.concatenate([c for c, _ in picked])[:n]
        best = np.concatenate([b for _, b in picked])[:n]
    err, gap = [], []
    for i, lg in ref.logits_rows(hs):
        at = jnp.take_along_axis(lg, _rows(chosen, i)[:, None], axis=1)[:, 0]
        err.append(np.abs(np.asarray(_rows(best, i) - at)))
        gap.append(np.asarray(jnp.max(lg, axis=1) - at))
    return {"err": np.concatenate(err)[:n], "gap": np.concatenate(gap)[:n]}
