"""Serving engine: slot-based continuous batching over prefill/decode.

A fixed decode batch of ``n_slots`` sequences shares one cache tree.
Requests are admitted into free slots; every ``step()`` decodes all
active slots at once; finished sequences free their slot.

Two serving-tier optimisations make the engine multi-caller fast:

* **Coalesced prefill** — admission drains the queue up to the free-slot
  count, groups the drained requests into micro-batches padded to
  power-of-two prompt-length buckets (the ``searchspace`` bucketing
  policy, so ``jax.jit`` retraces stay bounded — and are counted in
  ``prefill_retraces``), runs ONE batched prefill per group, and
  scatters the resulting cache rows into the slots with one batched
  insert.  Pad tokens sit *after* each prompt, so causal attention never
  lets a real token see them, and each decode step overwrites the one
  pad ring-slot that would otherwise become visible — tokens are
  bit-identical to batch=1 admission.  Recurrent stages (SSM / RWKV)
  carry prompt-order state, so those architectures coalesce by *exact*
  length (batched, never padded); same for the flash-attention prefill
  path, whose chunking depends on sequence length.

* **Batched sampling** — one argmax over the full active-slot logits
  batch (indexed on the host) plus at most one vmapped categorical for
  the temperature slots, instead of a ``logits[i:i+1]`` device sync per
  slot.  The per-slot RNG stream is preserved exactly: keys are split in
  the order the per-slot loop would have split them, and a vmapped
  ``jax.random.categorical`` over per-row keys produces the same bits as
  the row-at-a-time calls.

Tracing: ``step()`` writes ``jax.profiler.TraceAnnotation`` host spans
named ``serve.*`` (reap, admit, prefill, insert_cache, decode, sync,
bookkeep) with integer arguments, on the profiler's clock, so a trace
can say what the host was doing while the device idled; the two jitted
programs are named ``serve_prefill`` and ``serve_decode``.  While no
trace is active a span does not format its arguments and costs about a
microsecond of host time (docs/OPERATIONS.md).

Sampling: greedy or temperature.  The PPA activation tables run inside
both prefill and decode when the model config selects ``act_impl="ppa"``
— serving *is* the paper's deployment scenario, so the engine resolves
its activation tables through the :mod:`repro.compiler` table store
(memory -> disk -> compile) rather than compiling inline: a fleet of
engine processes sharing one artifact directory compiles each table once.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compiler import TableStore
from repro.faults import failpoint
from repro.models import (ModelCfg, ShardCtx, decode_step, init_cache,
                          make_model_acts, prefill)

__all__ = ["Request", "ServeEngine"]

_span = jax.profiler.TraceAnnotation

#: Smallest prompt-length bucket.  Below this every group shares one
#: trace; above it buckets double, so distinct padded shapes stay
#: O(log(max prompt len)).
_BUCKET_FLOOR = 8


def _bucket(n: int, lo: int = _BUCKET_FLOOR) -> int:
    """Smallest power-of-two >= n, floored at ``lo`` — the padded-shape
    policy ``repro.core.searchspace`` uses to bound jit retraces."""
    b = lo
    while b < n:
        b <<= 1
    return b


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    extra: Optional[dict] = None       # enc_feats / vision_embeds
    tenant: Optional[str] = None       # set by the multi-tenant front
    deadline_s: Optional[float] = None  # wall budget from submit()
    # filled by the engine:
    output: Optional[List[int]] = None
    done: bool = False
    timed_out: bool = False            # reaped past deadline_s
    rejected: Optional[str] = None     # shed reason ("queue_full", ...)
    t_submit: Optional[float] = None   # perf_counter at submit()
    t_first: Optional[float] = None    # first token emitted (admission)
    t_done: Optional[float] = None     # last token emitted (or shed/reap)


class ServeEngine:
    def __init__(self, cfg: ModelCfg, params, *, n_slots: int = 4,
                 cache_len: int = 256, ctx: Optional[ShardCtx] = None,
                 rng_seed: int = 0, table_store: Optional[TableStore] = None,
                 act_backend: Optional[str] = None, coalesce: bool = True,
                 max_queue: Optional[int] = None):
        # serving is the deployment hot path: ``act_backend`` overrides the
        # model config's activation execution backend (e.g. "pallas_fused"
        # to run quantize -> PPA -> dequantize -> gating in one kernel; see
        # repro.kernels.ops.available_backends()).
        if act_backend is not None and act_backend != cfg.act_backend:
            cfg = dataclasses.replace(cfg, act_backend=act_backend)
        self.cfg = cfg
        self.params = params
        # PPA activation tables resolve through the store: an engine given
        # its own store (e.g. a pinned deployment artifact directory) gets
        # a bundle built from it — the store is part of the bundle cache
        # key, so engines with different stores never share tables.
        self.table_store = table_store
        # pick up the per-device tuned config persisted next to the store
        # (fused block shape, jax search floors) BEFORE anything traces a
        # kernel — block shape is a trace-time static.  Zero flags: if no
        # config exists for this device, defaults stand.
        from repro.tune import activate_for_store
        self.tuned = activate_for_store(table_store) \
            if table_store is not None else None
        self.acts = make_model_acts(cfg, table_store)
        self.ctx = ctx or ShardCtx()
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.cache = init_cache(cfg, n_slots, cache_len)
        self.pos = np.zeros((n_slots,), np.int32)
        self.cur_tok = np.zeros((n_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.remaining = np.zeros((n_slots,), np.int32)
        self.rng = jax.random.PRNGKey(rng_seed)

        # named, so their HLO modules read jit_serve_decode and
        # jit_serve_prefill in a profiler trace
        def serve_decode(p, c, t, pos):
            return decode_step(p, cfg, c, t, pos, self.acts, self.ctx)

        def serve_prefill(p, batch, last):
            return prefill(p, cfg, batch, cache_len, self.acts, self.ctx,
                           last_idx=last)

        self._decode = jax.jit(serve_decode)
        self._prefill = jax.jit(serve_prefill)
        self.queue: Deque[Request] = collections.deque()
        # admission-control knobs: a bounded queue sheds (rejects) instead
        # of buffering unboundedly; per-request deadlines are reaped at
        # step start so an expired sequence frees its slot mid-decode.
        self.max_queue = max_queue
        self.shed = 0                   # rejected at submit (queue_full)
        self.timed_out = 0              # reaped past deadline_s
        self._has_deadlines = False     # skip the reap scan when unused
        self.coalesce = coalesce
        # padding is only sound when no stage carries prompt-order state
        # past the pads (SSM conv/h, RWKV time-mix) and prefill chunking
        # does not depend on sequence length (flash); otherwise groups
        # coalesce by exact prompt length — still batched, never padded.
        self._paddable = (cfg.attn_impl == "dense" and
                          all(st.kind not in ("hyb", "rwkv")
                              for st in cfg.stages))
        # pads must never enter a ring window: the serial path keeps the
        # last `eff` *real* positions, so a padded sequence longer than
        # the tightest window would evict real tokens in their favor.
        self._min_eff = min((cache_len if st.window is None
                             else min(st.window, cache_len))
                            for st in cfg.stages)
        self.prefill_retraces = 0           # distinct prefill shapes seen
        self._prefill_shapes: set = set()

    # ----------------------------------------------------------- admission
    def submit(self, req: Request) -> bool:
        """Enqueue ``req``; returns False when load-shed.

        With ``max_queue`` set, a full queue rejects instead of buffering:
        the request is finalised immediately (``done=True``, empty output,
        ``rejected="queue_full"``, latency stamped) so callers waiting on
        ``done`` never hang on a request the engine will not run."""
        req.output = []
        if req.t_submit is None:        # the tenant front stamps earlier
            req.t_submit = time.perf_counter()
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            req.rejected = "queue_full"
            req.done = True
            req.t_done = time.perf_counter()
            self.shed += 1
            return False
        if req.deadline_s is not None:
            self._has_deadlines = True
        self.queue.append(req)
        return True

    def _reap_deadlines(self) -> int:
        """Expire requests past their deadline; returns how many.

        Queued requests are dropped before admission; active ones free
        their slot mid-decode (partial output is kept on the request)."""
        now = time.perf_counter()

        def _expired(r: Request) -> bool:
            return (r.deadline_s is not None and r.t_submit is not None
                    and now - r.t_submit > r.deadline_s)

        n = 0
        if any(_expired(r) for r in self.queue):
            kept: Deque[Request] = collections.deque()
            for r in self.queue:
                if _expired(r):
                    r.timed_out = True
                    r.done = True
                    r.t_done = now
                    n += 1
                else:
                    kept.append(r)
            self.queue = kept
        for i, r in enumerate(self.slot_req):
            if r is not None and _expired(r):
                r.timed_out = True
                r.done = True
                r.t_done = now
                self.slot_req[i] = None
                self.remaining[i] = 0
                n += 1
        self.timed_out += n
        return n

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _bucket_len(self, prompt_len: int) -> int:
        """Padded token length for a prompt (== prompt_len when padding
        is unsound for this config or would overflow a ring window)."""
        if not self._paddable:
            return prompt_len
        b = _bucket(prompt_len)
        if self.cfg.vision_tokens + b > self._min_eff:
            return prompt_len
        return b

    def _admit(self) -> None:
        """Admit queued requests into free slots, under a ``serve.admit``
        span whose args are the requests admitted (``rows``) and the
        prefills run for them (``groups``)."""
        with _span("serve.admit") as span:
            free = self._free_slots()
            n = min(len(free), len(self.queue))
            # FIFO -> slot mapping identical to per-request admission
            pairs = [(free[j], self.queue.popleft()) for j in range(n)]
            # pre-split sampling keys in FIFO order: the RNG stream must
            # not depend on how requests group into prefill micro-batches
            keys: Dict[int, jax.Array] = {}
            for _, req in pairs:
                if req.temperature > 0:
                    self.rng, k = jax.random.split(self.rng)
                    keys[id(req)] = k
            if not self.coalesce:
                for slot, req in pairs:
                    self._admit_serial(slot, req, keys.get(id(req)))
                span.set_metadata(rows=n, groups=n)
                return
            groups: Dict[tuple, list] = {}
            for slot, req in pairs:
                sig = (self._bucket_len(len(req.prompt)),
                       tuple(sorted(req.extra)) if req.extra else ())
                groups.setdefault(sig, []).append((slot, req))
            for (blen, _), members in groups.items():
                self._admit_group(blen, members, keys)
            span.set_metadata(rows=n, groups=len(groups))

    def _admit_serial(self, slot: int, req: Request,
                      key: Optional[jax.Array]) -> None:
        """Batch=1 admission — the serial baseline path (and the exact
        pre-coalescing engine behaviour the tests pin tokens against)."""
        lp = len(req.prompt)
        with _span("serve.prefill", bucket=lp, rows=1, real_tokens=lp,
                   padded_tokens=lp, rids=(req.rid,)):
            batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
            if req.extra:
                batch.update({k: jnp.asarray(v[None]) for k, v in
                              req.extra.items()})
            logits, cache1 = prefill(self.params, self.cfg, batch,
                                     self.cache_len, self.acts, self.ctx)
        with _span("serve.sync", rows=1, phase=0):
            if key is None:
                # serial-baseline contract: one sync per admitted request
                # IS the behaviour the coalesced path is benchmarked
                # against.  analysis: allow(host-sync)
                tok = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
            else:
                # analysis: allow(host-sync) — see above; same contract
                tok = int(np.asarray(jax.random.categorical(
                    key, logits / req.temperature, axis=-1))[0])
        self._insert_cache([slot], cache1, [0])
        self._start_slot(slot, req, tok)

    def _admit_group(self, blen: int, members: Sequence[Tuple[int, Request]],
                     keys: Dict[int, jax.Array]) -> None:
        """One batched prefill for every (slot, request) in ``members``,
        padded on the right to the shared ``blen`` token bucket."""
        g = len(members)
        real = sum(len(req.prompt) for _, req in members)
        with _span("serve.prefill", bucket=blen, rows=g, real_tokens=real,
                   padded_tokens=blen * g,
                   rids=tuple(req.rid for _, req in members)):
            toks = np.zeros((g, blen), np.int32)
            last = np.zeros((g,), np.int32)
            for j, (_, req) in enumerate(members):
                lp = len(req.prompt)
                toks[j, :lp] = req.prompt
                last[j] = self.cfg.vision_tokens + lp - 1
            batch = {"tokens": jnp.asarray(toks)}
            extra = members[0][1].extra
            if extra:
                for k in extra:
                    batch[k] = jnp.asarray(
                        np.stack([req.extra[k] for _, req in members]))
            sig = (blen, g, tuple(sorted(extra)) if extra else ())
            if sig not in self._prefill_shapes:
                self._prefill_shapes.add(sig)
                self.prefill_retraces += 1
            logits, cache1 = self._prefill(self.params, batch,
                                           jnp.asarray(last))
        with _span("serve.sync", rows=g, phase=0):
            toks_out = self._sample_rows(
                logits,
                [req.temperature for _, req in members],
                [keys.get(id(req)) for _, req in members])
        self._insert_cache([s for s, _ in members], cache1, list(range(g)))
        for j, (slot, req) in enumerate(members):
            self._start_slot(slot, req, int(toks_out[j]))

    def _start_slot(self, slot: int, req: Request, tok: int) -> None:
        t = len(req.prompt) + self.cfg.vision_tokens
        self.pos[slot] = t
        self.cur_tok[slot] = tok
        self.remaining[slot] = req.max_new_tokens - 1
        req.output.append(tok)
        req.t_first = time.perf_counter()
        self.slot_req[slot] = req

    def _insert_cache(self, slots: Sequence[int], cache1,
                      rows: Sequence[int]) -> None:
        """Scatter prefill cache rows ``rows`` into slot rows ``slots``
        with one batched dynamic update per cache leaf.

        Cache leaves have layout (L, B, ...) per stage."""
        with _span("serve.insert_cache", rows=len(slots)):
            sl = jnp.asarray(np.asarray(slots, np.int32))
            rw = jnp.asarray(np.asarray(rows, np.int32))

            def ins(full, one):
                return full.at[:, sl].set(one[:, rw].astype(full.dtype))
            self.cache = jax.tree_util.tree_map(ins, self.cache, cache1)

    # ------------------------------------------------------------ sampling
    def _sample_rows(self, logits: jax.Array, temps: Sequence[float],
                     keys: Sequence[Optional[jax.Array]]) -> np.ndarray:
        """Sample one token per logits row (B, V) -> np (B,).

        Greedy rows share ONE argmax launch and one host transfer;
        temperature rows share one vmapped categorical over their per-row
        keys (bit-identical to row-at-a-time ``jax.random.categorical``).
        At most two device->host syncs regardless of row count.
        """
        out = np.zeros((len(temps),), np.int64)
        t_rows = [j for j, k in enumerate(keys) if k is not None]
        if len(t_rows) < len(temps):
            # documented contract: sync #1 of <= 2 (all greedy rows).
            # analysis: allow(host-sync)
            out[:] = np.asarray(jnp.argmax(logits, axis=-1))
        if t_rows:
            idx = np.asarray(t_rows, np.int32)
            kk = jnp.stack([keys[j] for j in t_rows])
            tt = jnp.asarray(np.asarray([temps[j] for j in t_rows],
                                        np.float32))
            samp = jax.vmap(
                lambda k, l, t: jax.random.categorical(k, l / t, axis=-1))(
                    kk, logits[jnp.asarray(idx)], tt)
            # documented contract: sync #2 of <= 2 (all sampled rows).
            # analysis: allow(host-sync)
            out[idx] = np.asarray(samp)
        return out

    def _sample(self, logits: jax.Array, temperature: float) -> np.ndarray:
        """Single-call sampling (kept for external callers/tests)."""
        if temperature <= 0:
            # external single-call API returns host tokens by contract.
            # analysis: allow(host-sync)
            return np.asarray(jnp.argmax(logits, axis=-1))
        self.rng, k = jax.random.split(self.rng)
        # analysis: allow(host-sync) — same single-call contract
        return np.asarray(
            jax.random.categorical(k, logits / temperature, axis=-1))

    # ---------------------------------------------------------------- step
    def step(self) -> int:
        """Admit pending requests, decode one token for every active slot.

        Returns the number of active sequences stepped."""
        failpoint("serve.decode.step")
        if self._has_deadlines:
            with _span("serve.reap") as span:
                span.set_metadata(n=self._reap_deadlines())
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        with _span("serve.decode", active=len(active)):
            toks = jnp.asarray(self.cur_tok[:, None], jnp.int32)
            pos = jnp.asarray(self.pos, jnp.int32)
            logits, self.cache = self._decode(self.params, self.cache, toks,
                                              pos)
            # split keys per active temperature slot, in slot order — the
            # same stream the per-slot sampling loop consumed
            temps: List[float] = []
            keys: List[Optional[jax.Array]] = []
            for i in active:
                t = self.slot_req[i].temperature
                temps.append(t)
                if t > 0:
                    self.rng, k = jax.random.split(self.rng)
                    keys.append(k)
                else:
                    keys.append(None)
            rows_logits = logits[jnp.asarray(active)]
        with _span("serve.sync", rows=len(active), phase=1):
            sampled = self._sample_rows(rows_logits, temps, keys)
        with _span("serve.bookkeep") as span:
            nxt = np.zeros((self.n_slots,), np.int32)
            now = time.perf_counter()
            finished = 0
            for j, i in enumerate(active):
                req = self.slot_req[i]
                tok = int(sampled[j])
                nxt[i] = tok
                req.output.append(tok)
                self.pos[i] += 1
                self.remaining[i] -= 1
                if self.remaining[i] <= 0:
                    req.done = True
                    req.t_done = now
                    self.slot_req[i] = None
                    finished += 1
            self.cur_tok = nxt
            span.set_metadata(finished=finished)
        return len(active)

    # -------------------------------------------------------------- warmup
    def warmup(self, prompt_lens: Sequence[int] = (), *,
               batch: int = 1, decode: bool = True) -> int:
        """Pre-trace the serving jits without touching engine state.

        Runs one batched prefill per (bucketed) prompt length — which
        also resolves and packs every activation table the model will
        touch — plus one decode step whose outputs are discarded.  A
        tenant warmed this way pays trace+table cost at admission, not on
        its first request.  Returns the number of traces run.
        """
        n = 0
        for lp in prompt_lens:
            blen = self._bucket_len(lp)
            batch_d = {"tokens": jnp.zeros((batch, blen), jnp.int32)}
            extra_keys = []
            if self.cfg.enc_layers:
                extra_keys.append("enc_feats")
                batch_d["enc_feats"] = jnp.zeros(
                    (batch, self.cfg.enc_seq, self.cfg.d_model), jnp.float32)
            if self.cfg.vision_tokens:
                extra_keys.append("vision_embeds")
                batch_d["vision_embeds"] = jnp.zeros(
                    (batch, self.cfg.vision_tokens, self.cfg.d_model),
                    jnp.float32)
            sig = (blen, batch, tuple(sorted(extra_keys)))
            if sig not in self._prefill_shapes:
                self._prefill_shapes.add(sig)
                self.prefill_retraces += 1
            last = jnp.full((batch,),
                            self.cfg.vision_tokens + min(lp, blen) - 1,
                            jnp.int32)
            logits, _ = self._prefill(self.params, batch_d, last)
            jax.block_until_ready(logits)
            n += 1
        if decode:
            logits, _ = self._decode(
                self.params, self.cache,
                jnp.zeros((self.n_slots, 1), jnp.int32),
                jnp.zeros((self.n_slots,), jnp.int32))
            jax.block_until_ready(logits)
            n += 1
        return n

    def stats(self) -> Dict[str, int]:
        """Load/health counters for operators and the tenant front."""
        return {
            "queue_depth": len(self.queue),
            "active_slots": sum(r is not None for r in self.slot_req),
            "n_slots": self.n_slots,
            "max_queue": self.max_queue if self.max_queue is not None else -1,
            "shed": self.shed,
            "timed_out": self.timed_out,
            "prefill_retraces": self.prefill_retraces,
        }

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                return
