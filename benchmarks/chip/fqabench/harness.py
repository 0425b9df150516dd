"""Run one cell of ``BENCHMARK.json`` once, on the chip.

Everything a cell needs is found by name from files: its configuration
in ``configs/<config>.json``, with the program module it names in
``programs/<program>.py`` (how the program is built from the file, its
weight rules beyond the dense ones, its work counts) and the plain
reference it names in ``reference/<name>.py``; its traffic in
``traffic/<mix>.json``, the limits of its correctness check in
``limits/<workload>.json``, and each per-layer metric's reader in
``layer_metrics/<metric>.py``.  A later configuration of any family,
cell, mix or metric is added by adding files.

A run: set-up (tables, weights, engine, warm-up of every shape the mix
can cause), one measured window of ``--seconds`` driving
``ServeEngine.submit()`` / ``step()``, then the check of the served
tokens against the reference, and one JSON line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import tracing, yardstick
from .traffic import Mix, check_sample

clock = time.perf_counter
BENCH_DIR = Path(__file__).resolve().parents[1]
#: seconds at the end of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 8.0
#: what ``Cell.readings`` reads; a limits file names some of them
READINGS = ("logit_err_max", "logit_err_rms", "token_gap_max")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ lookup by name
def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered first, as an import would, so that a dataclass in it
    # can resolve its annotations
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_config(bench_dir: Path, name: str) -> dict:
    conf = json.loads((bench_dir / "configs" / f"{name}.json").read_text())
    if conf.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {conf.get('name')!r}")
    return conf


def load_mix(bench_dir: Path, name: str) -> Mix:
    return Mix(name, json.loads(
        (bench_dir / "traffic" / f"{name}.json").read_text()))


def load_limits(bench_dir: Path, workload: str) -> dict:
    return json.loads((bench_dir / "limits" / f"{workload}.json").read_text())


def load_reader(bench_dir: Path, metric: str) -> Callable:
    return _load_module(bench_dir / "layer_metrics" / f"{metric}.py",
                        f"layer_metric_{metric}").read


def load_reference(bench_dir: Path, name: str):
    return _load_module(bench_dir / "reference" / f"{name}.py",
                        f"reference_{name}")


def load_program(bench_dir: Path, conf: dict):
    """The program module that configuration ``conf`` names under
    ``"program"``: ``programs/<program>.py``, with ``program_cfg(conf)``
    (the program's ModelCfg), ``program_params(cfg, seed)`` (its
    parameter tree), ``counts(conf)`` (an object with ``prefill_flops``,
    ``decode_flops`` and ``decode_step_bytes``) and, optionally,
    ``ROLES`` (weight rules beyond the dense roles)."""
    from . import weights

    where = f"configs/{conf.get('name')}.json"
    if "program" not in conf:
        raise KeyError(f"{where} names no program: give \"program\": "
                       f"\"<module>\" for {bench_dir}/programs/<module>.py")
    path = bench_dir / "programs" / f"{conf['program']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{where} names program "
                                f"{conf['program']!r}, and there is no {path}")
    mod = _load_module(path, f"program_{conf['program']}")
    weights.check_extra(getattr(mod, "ROLES", {}))
    return mod


def weight_rules(bench_dir: Path, conf: dict) -> dict:
    """The weight rules beyond the dense roles of the program that
    ``conf`` names (``ROLES`` of its module), for a reference to draw the
    program's bits."""
    return getattr(load_program(bench_dir, conf), "ROLES", {})


def cell_metrics(spec: dict, workload: str, section: str) -> List[dict]:
    """The metrics of ``section`` that cell ``workload`` reports."""
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


# ----------------------------------------------------------------- records
@dataclasses.dataclass
class Step:
    """One ``step()`` call inside the window (seconds from its start)."""

    t0: float
    t1: float
    queue_before: int
    prefill_lens: List[int]       # prompts admitted by this step
    decode_keys: List[int]        # keys attended by each decoded token


@dataclasses.dataclass
class Run:
    """What a per-layer reader gets."""

    counts: yardstick.Counts
    peaks: dict
    window_s: float
    steps: List[Step]
    traced_from: Optional[float]          # window offset the trace began
    trace: Optional[tracing.Summary]

    def traced_steps(self) -> List[Step]:
        if self.traced_from is None:
            return []
        return [s for s in self.steps if s.t0 >= self.traced_from]


# ------------------------------------------------------------ the logit tap
class LogitTap:
    """Keeps, for every row the engine samples a token from, its best
    logit and the request the row belongs to.

    It wraps the engine's ``_sample_rows`` (every greedy token is chosen
    there, from the logits of the prefill or the decode step) and
    ``_admit_group`` (whose rows are its members; a decode step's rows
    are the active slots in slot order).  The best logit is one reduction
    dispatched beside the engine's own argmax and read after the window,
    so the timed path waits for nothing more."""

    def __init__(self, eng):
        import jax.numpy as jnp
        self.calls: list = []
        self._members = None
        sample, admit_group = eng._sample_rows, eng._admit_group

        def admit(blen, members, keys):
            self._members = [r for _, r in members]
            try:
                return admit_group(blen, members, keys)
            finally:
                self._members = None

        def tapped(logits, temps, keys):
            rows = self._members
            if rows is None:
                rows = [r for r in eng.slot_req if r is not None]
            if len(rows) != logits.shape[0]:
                raise RuntimeError(f"logit tap: {logits.shape[0]} rows "
                                   f"sampled for {len(rows)} requests")
            self.calls.append((jnp.max(logits, axis=-1), rows))
            return sample(logits, temps, keys)

        eng._admit_group, eng._sample_rows = admit, tapped

    def best(self, reqs: list) -> Dict[int, np.ndarray]:
        """The best logit of each served token of ``reqs``, by ``rid``;
        fails unless the tap saw every one of them."""
        import jax
        want = {id(r) for r in reqs}
        calls = [(v, rows) for v, rows in self.calls
                 if any(id(r) in want for r in rows)]
        got: Dict[int, list] = {id(r): [] for r in reqs}
        for vals, rows in zip(jax.device_get([v for v, _ in calls]),
                              [rows for _, rows in calls]):
            for v, r in zip(np.asarray(vals, np.float32), rows):
                if id(r) in got:
                    got[id(r)].append(v)
        out = {}
        for r in reqs:
            if len(got[id(r)]) != len(r.output):
                raise RuntimeError(
                    f"logit tap saw {len(got[id(r)])} logits for request "
                    f"{r.rid}, which served {len(r.output)} tokens")
            out[r.rid] = np.asarray(got[id(r)], np.float32)
        return out


# -------------------------------------------------------------- the window
class _Window:
    """Drives the engine through one measured window and stamps every
    token when the ``step()`` that produced it returns."""

    def __init__(self, eng, seconds: float, trace_dir: Optional[str]):
        import jax
        self.jax = jax
        self.eng = eng
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t0 = 0.0
        self.steps: List[Step] = []
        self.due: Dict[int, float] = {}
        self.stamps: Dict[int, List[float]] = {}
        self.lag: List[float] = []
        self.inflight: list = []
        self.submitted: list = []
        self.traced_from: Optional[float] = None
        self._traced = None

    def _span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def submit(self, req, due: float) -> None:
        self.due[req.rid] = due
        self.stamps[req.rid] = []
        self.lag.append(clock() - due)
        self.eng.submit(req)
        self.inflight.append(req)
        self.submitted.append(req)

    def step(self) -> None:
        before = [len(r.output) for r in self.inflight]
        qb = self.eng.stats()["queue_depth"]
        t0 = clock()
        with self._span("engine.step"):
            self.eng.step()
        t1 = clock()
        pre, keys, keep = [], [], []
        for r, n0 in zip(self.inflight, before):
            n1 = len(r.output)
            self.stamps[r.rid].extend([t1] * (n1 - n0))
            if n0 == 0 and n1 > 0:
                pre.append(len(r.prompt))
            keys.extend(len(r.prompt) + k for k in range(max(n0, 1), n1))
            if not r.done:
                keep.append(r)
        self.inflight = keep
        self.steps.append(Step(t0 - self.t0, t1 - self.t0, qb, pre, keys))

    def _maybe_trace(self, now: float) -> None:
        if (self.trace_dir is not None and self._traced is None
                and now - self.t0 >= self.seconds - TRACE_SECONDS):
            self.jax.profiler.start_trace(self.trace_dir)
            self._traced = self._span(tracing.WINDOW_SPAN)
            self._traced.__enter__()
            self.traced_from = clock() - self.t0

    def _end(self) -> float:
        t_end = clock()
        if self._traced is not None:
            self._traced.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
        return t_end

    def open_loop(self, reqs: list, offsets: List[float]) -> float:
        self.t0 = clock()
        due = [self.t0 + o for o in offsets]
        i, n = 0, len(reqs)
        while True:
            now = clock()
            self._maybe_trace(now)
            if now - self.t0 >= self.seconds:
                break
            with self._span("bench.submit"):
                while i < n and due[i] <= now:
                    self.submit(reqs[i], due[i])
                    i += 1
            st = self.eng.stats()
            if st["queue_depth"] or st["active_slots"]:
                self.step()
            else:
                with self._span("bench.poll"):
                    nxt = due[i] if i < n else self.t0 + self.seconds
                    time.sleep(max(0.0, min(nxt, self.t0 + self.seconds)
                                   - clock()))
        return self._end()

    def backlog(self, make_req: Callable, depth: int) -> float:
        self.t0 = clock()
        while True:
            now = clock()
            self._maybe_trace(now)
            if now - self.t0 >= self.seconds:
                break
            with self._span("bench.submit"):
                for _ in range(depth - self.eng.stats()["queue_depth"]):
                    self.submit(make_req(), clock())
            self.step()
        return self._end()


def e2e_values(w: _Window, t_end: float) -> Dict[str, float]:
    """Every end-to-end quantity a window gives, by metric name."""
    window_s = t_end - w.t0
    n_tok = sum(len(s) for s in w.stamps.values())
    ttft, itl = [], []
    for r in w.submitted:
        st = w.stamps[r.rid]
        ttft.append((st[0] if st else t_end) - w.due[r.rid])
        itl.extend(np.diff(st))
    return {"out_tokens_per_s": n_tok / window_s,
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "itl_p99_ms": 1e3 * float(np.percentile(itl, 99))}


# ----------------------------------------------------------------- set-up
class _CompileCounter:
    """Counts the executables JAX builds or loads (one event each), and
    how many of them the persistent compilation cache supplied."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = self.cache_hits = 0
        self.event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, *_a, **_k) -> None:
        if event == self.event:
            self.n += 1

    def _on_event(self, event: str, *_a, **_k) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _tables(act_impl: str, root: Path):
    from repro.compiler import CompileJob, TableStore, compile_batch
    from repro.models.activations import ppa_table_jobs

    store = TableStore(str(root))
    triples = ppa_table_jobs(act_impl)
    compile_batch([CompileJob(n, c, s) for n, c, s in triples], store=store)
    for naf, cfg, scheme in triples:
        store.compile_or_load(naf, cfg, scheme)
    return store


def _preload(eng, shapes) -> None:
    """Build or load the engine's decode program and its prefill program
    of every (bucket, group size) at once, on threads: each holds fused
    PPA kernels and takes seconds to build or load on the chip, most of
    it outside Python.  The serving warm-up then finds them built."""
    import jax
    import jax.numpy as jnp

    prefill = getattr(eng, "_prefill", None)
    decode = getattr(eng, "_decode", None)
    if not (hasattr(prefill, "lower") and hasattr(decode, "lower")):
        return
    i32 = jnp.int32
    jobs = [lambda: decode.lower(
        eng.params, eng.cache, jax.ShapeDtypeStruct((eng.n_slots, 1), i32),
        jax.ShapeDtypeStruct((eng.n_slots,), i32)).compile()]
    for blen, _, g in shapes:
        jobs.append(lambda blen=blen, g=g: prefill.lower(
            eng.params, {"tokens": jax.ShapeDtypeStruct((g, blen), i32)},
            jax.ShapeDtypeStruct((g,), i32)).compile())
    with ThreadPoolExecutor(max_workers=8) as pool:
        for f in [pool.submit(job) for job in jobs]:
            f.result()


def warm_up(eng, mix: Mix) -> int:
    """Serve, through ``submit()``/``step()``, one admission group of
    every (bucket, group size) the mix can cause, each until it drains.
    That compiles every prefill shape, the decode step and the eager
    cache inserts and gathers each group size uses."""
    from repro.serve import Request

    shapes = mix.warm_shapes(eng._bucket_len)
    t = clock()
    _preload(eng, shapes)
    log(f"set-up: {len(shapes)} prefill programs built or loaded on "
        f"threads in {clock() - t:.2f} s")
    rid = -1
    for _, plen, g in shapes:
        for _ in range(g):
            eng.submit(Request(rid=rid, prompt=np.zeros(plen, np.int32),
                               max_new_tokens=2))
            rid -= 1
        eng.run_until_drained()
    return len(shapes)


def _device_check(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"devices: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU here (platform {d0.platform!r}); "
                         "nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, found {len(devs)}")
    return devs


# -------------------------------------------------------------------- cell
class Cell:
    """One cell of ``BENCHMARK.json``, set up: its files found by name,
    the engine built on weights drawn from ``seed`` and warmed up on every
    shape its traffic can cause.

    ``require_tpu=False`` and ``engine_hook`` (called on the engine before
    the warm-up) are for the tests, which drive the pipeline on the CPU."""

    def __init__(self, workload: str, seed: int, *,
                 root: Optional[Path] = None, bench_dir: Path = BENCH_DIR,
                 require_tpu: bool = True,
                 engine_hook: Optional[Callable] = None):
        root = bench_dir.parents[1] if root is None else root
        self.spec = json.loads((root / "BENCHMARK.json").read_text())
        wl = next((w for w in self.spec["workloads"]
                   if w["name"] == workload), None)
        if wl is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.wl, self.bench_dir = wl, bench_dir
        self.conf = load_config(bench_dir, wl["config"])
        self.mix = load_mix(bench_dir, wl["traffic"])
        self.limits = load_limits(bench_dir, wl["name"])
        self.ref_mod = load_reference(bench_dir, self.conf["reference"])
        self.program = load_program(bench_dir, self.conf)

        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(bench_dir / ".jax_cache"))
        # keep every program, the engine's small eager ones too: each takes
        # about a second to build on the chip, and there are hundreds
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.devs = (_device_check(wl["chips"]) if require_tpu
                     else jax.devices())
        kind = self.devs[0].device_kind
        self.peaks = yardstick.peaks(kind if require_tpu else "TPU v5e")

        from repro.serve import ServeEngine
        self.counter = _CompileCounter()
        self.cfg = self.program.program_cfg(self.conf)
        self.counts = self.program.counts(self.conf)
        t = clock()
        store = _tables(self.cfg.act_impl, bench_dir / ".tables")
        log(f"set-up: tables {clock() - t:.2f} s")
        t = clock()
        params = self.program.program_params(self.cfg, seed)
        jax.block_until_ready(params)
        log(f"set-up: weights {clock() - t:.2f} s")
        t = clock()
        self.eng = ServeEngine(self.cfg, params, n_slots=self.mix.n_slots,
                               cache_len=self.mix.cache_len,
                               table_store=store)
        if engine_hook is not None:
            engine_hook(self.eng)
        self.tap = LogitTap(self.eng)
        log(f"set-up: engine {clock() - t:.2f} s")
        t = clock()
        n_warm = warm_up(self.eng, self.mix)
        log(f"set-up: warm-up of {n_warm} (bucket, group) shapes "
            f"{clock() - t:.2f} s; {self.counter.n} programs built or "
            f"loaded, {self.counter.cache_hits} from the persistent cache")

    def reseed(self, seed: int) -> None:
        """Serve weights drawn from ``seed`` (for tools that read many
        seeds in one process)."""
        import jax
        self.eng.params = None
        self.eng.params = self.program.program_params(self.cfg, seed)
        jax.block_until_ready(self.eng.params)

    def measure(self, seed: int, seconds: float,
                trace_dir: Optional[str] = None,
                mix: Optional[Mix] = None):
        """One window; returns it, its end, and the programs compiled or
        loaded inside it."""
        from repro.serve import Request

        mix = self.mix if mix is None else mix
        vocab = self.conf["vocab_size"]
        n0, r0 = self.counter.n, self.eng.prefill_retraces
        self.tap.calls.clear()
        w = _Window(self.eng, seconds, trace_dir)
        if mix.kind == "open_loop":
            jobs = mix.open_loop(seed, seconds, vocab)
            reqs = [Request(rid=j.index, prompt=j.prompt,
                            max_new_tokens=j.max_new_tokens) for j in jobs]
            t_end = w.open_loop(reqs, [j.due_s for j in jobs])
        else:
            stream = mix.stream(seed, vocab)

            def make_req():
                j = next(stream)
                return Request(rid=j.index, prompt=j.prompt,
                               max_new_tokens=j.max_new_tokens)
            t_end = w.backlog(make_req, mix.backlog)
        compiles = (self.counter.n - n0) + (self.eng.prefill_retraces - r0)
        log(f"window {t_end - w.t0:.3f} s: {len(w.steps)} steps, "
            f"{len(w.submitted)} requests submitted, "
            f"{sum(r.done for r in w.submitted)} finished; "
            f"compiles inside the window: {compiles}")
        lag = np.asarray(w.lag) * 1e3
        log(f"generator lag ms: p50 {np.percentile(lag, 50):.3f} "
            f"p95 {np.percentile(lag, 95):.3f} max {lag.max():.3f}")
        return w, t_end, compiles

    def sample(self, w: "_Window", seed: int) -> list:
        """(prompt, served tokens, the engine's best logit at each) of the
        requests the check reads."""
        finished = [r for r in w.submitted if r.done and not r.rejected
                    and not r.timed_out]
        picked = check_sample(finished, int(self.mix.spec["check_requests"]),
                              seed)
        best = self.tap.best(picked)
        self.tap.calls.clear()
        return [(np.asarray(r.prompt, np.int32), list(r.output), best[r.rid])
                for r in picked]

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devs[:self.wl["chips"]])

    def free(self) -> None:
        """Drop the program's weights and cache from the device."""
        self.eng.cache = self.eng.params = None
        self.eng = None
        gc.collect()

    def readings(self, seed: int, seqs: list,
                 control: bool = False) -> Dict[str, float]:
        """Over every served token of ``seqs``, against the float32
        reference: the widest and the root-mean-square gap between the
        engine's best logit and the reference's logit of the token served
        (``logit_err_max``, ``logit_err_rms``), and the widest gap of that
        reference logit below the reference's best (``token_gap_max``).
        ``control`` puts the int8 reference in the engine's place
        (``reference/<name>.py``: ``compare``)."""
        mix = self.mix.spec
        length = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
        got = self.ref_mod.compare(self.conf, seed, seqs,
                                   int(mix["check_requests"]), length,
                                   control=control)
        err = got["err"].astype(np.float64)
        stats = {"logit_err_max": float(err.max()),
                 "logit_err_rms": float(np.sqrt(np.mean(err * err))),
                 "token_gap_max": float(got["gap"].max())}
        log(f"check{' (control)' if control else ''}: {len(err)} served "
            f"tokens, {np.count_nonzero(got['gap'])} not the reference's "
            f"first choice; {stats}")
        return stats

    def check(self, seed: int, seqs: list,
              control: bool = False) -> Dict[str, dict]:
        """The readings that the cell's limits file names, each beside
        its limit."""
        return self.limited(self.readings(seed, seqs, control))

    def limited(self, stats: Dict[str, float]) -> Dict[str, dict]:
        return {k: {"value": stats[k], "limit": float(v["limit"])}
                for k, v in self.limits.items()}


def judge(compared: Dict[str, dict]) -> bool:
    """Correct when every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in compared.values())


# -------------------------------------------------------------------- main
def run(argv=None, *, t_start: Optional[float] = None, **cell_kw) -> dict:
    """One run of one cell; returns the result line as a dict."""
    t_start = clock() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload, args.seed, **cell_kw)
    section = "per_layer" if args.trace else "end_to_end"
    wanted = cell_metrics(cell.spec, args.workload, section)
    readers = {m["name"]: load_reader(cell.bench_dir, m["name"])
               for m in wanted} if args.trace else {}
    trace_dir = tempfile.mkdtemp(prefix="fqabench-trace-") \
        if args.trace else None
    setup_s = clock() - t_start
    w, t_end, compiles = cell.measure(args.seed, args.seconds, trace_dir)

    summary = None
    if trace_dir is not None:
        try:
            summary = tracing.reduce_trace(tracing.read_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    mem_peak = cell.memory_peak()
    seqs = cell.sample(w, args.seed)
    failed_engine = sum(bool(r.rejected) or r.timed_out for r in w.submitted)
    w.eng = None
    cell.free()
    t_ref = clock()
    compared = {"compiles_in_window": {"value": compiles, "limit": 0}}
    if seqs:
        compared.update(cell.check(args.seed, seqs))
    log(f"reference check of {len(seqs)} requests: {clock() - t_ref:.1f} s")
    ok = bool(seqs) and judge(compared)

    metrics = {}
    if args.trace:
        rec = Run(cell.counts, cell.peaks, t_end - w.t0, w.steps,
                  w.traced_from, summary)
        for m in wanted:
            v = readers[m["name"]](rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        vals = e2e_values(w, t_end)
        vals["setup_s"] = setup_s
        log(f"window values: {vals}")
        for m in wanted:
            metrics[m["name"]] = {"value": float(vals[m["name"]]),
                                  "unit": m["unit"]}
    d0 = cell.devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(cell.devs), "memory_peak_bytes": mem_peak}
    out = {"correct": ok, "attempted": len(w.submitted),
           "failed": failed_engine + (0 if ok else len(seqs)),
           "metrics": metrics,
           "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["compared"] = compared
    for name, c in compared.items():
        print(f"{name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    out = run(argv, t_start=t_start)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0
