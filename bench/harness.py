"""One run of one cell: set-up, the measured window on the wall clock, the
check of what the window served, and the cell's metrics.

Everything that belongs to a configuration, a traffic mix or a metric is
found by its name in ``BENCHMARK.json``: ``bench/configs/<config>.json``
with its plain reference ``bench/models/<model_type>.py``,
``bench/traffic/<mix>.json``, and ``bench/metrics/<metric>.py``.

The served path is wired as ``repro.launch.serve.run_engine`` wires it:
a ``RealModelExecutor`` on the first device behind
``Engine(Topology.serving(2, 1), SpecializedPolicy(), ServeConfig(...))``.
The harness owns the event loop. It takes the engine's events through
the engine's event sink, handles each at ``max(due, now)`` on the wall
clock, and waits for the clock where the next event is not yet due. One
host thread makes one device call at a time, as the chip runs one. Every
latency is taken from the harness's own timestamps: a request from its
due time, a token when the executor returns it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import importlib.util
import itertools
import json
import os
import shutil
import time
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np

from bench import reference, trace, traffic
from bench.weights import make_weights
from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.launch.serve import RealModelExecutor, engine_freq_config
from repro.models.api import build_model
from repro.sched import SpecializedPolicy, Topology
from repro.sched.engine import Engine, Request, ServeConfig

# run_engine passes serve's --batch, whose default is 8
DECODE_BATCH_MAX = 8
# the traced part of a --trace 1 run: this many seconds in the middle of
# the window (two whole cycles of an on/off mix of 6 s)
TRACE_SECONDS = 12.0


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def load_metric(root: Path, name: str):
    """``bench/metrics/<name>.py``: a module with ``read(run)`` that
    returns the metric's value, or ``None`` where there is nothing to
    read."""
    return load_metric_file(root / "bench" / "metrics" / f"{name}.py")


def load_metric_file(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(root: Path, kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def chip_error(cell: dict):
    """Why this machine cannot run ``cell``, or ``None``: it needs TPU
    chips, as many as the cell asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX found {devs[0].platform}"
    if len(devs) < cell["chips"]:
        return f"{cell['chips']} chips asked for, {len(devs)} found"
    return None


def enable_cache(root: Path):
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every program,
    small ones too, so that only a cell's first run compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def program_arch(cfg: dict):
    """The program's configuration of a configuration file: the registry
    architecture with the file's published numbers."""
    arch = dataclasses.replace(
        get_arch(cfg["registry"]),
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["serve_dtype"], compute_dtype=cfg["serve_dtype"])
    if arch.resolved_head_dim * arch.n_heads != arch.d_model:
        raise ValueError(f"{cfg['registry']}: head size is not "
                         "hidden_size / num_attention_heads")
    return arch


# --------------------------------------------------------------- records


class Log:
    """The harness's clock and what it saw: every executor call and every
    token's time, in ms since the window opened."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.t0 = None
        self.calls = []                  # (kind, start, end, rids, lens)
        self.tokens = defaultdict(list)  # rid -> times the tokens came back
        self.prefill_start = {}          # rid -> start of its prefill call

    def open(self):
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


class BenchExecutor(RealModelExecutor):
    """The program's executor, fed the benchmark's prompts and timed."""

    def __init__(self, model, params, vocab, prompt_len, max_seq, device,
                 seed, log: Log):
        super().__init__(model, params, vocab, prompt_len, max_seq, device,
                         seed=seed)
        self.log = log

    def prompt(self, rid: int) -> np.ndarray:
        return traffic.prompt(self.seed, rid, self.prompt_len, self.vocab)

    def prefill(self, req, chunk, pool, ndev):
        log = self.log
        t0 = log.now()
        with log.span("executor.prefill"):
            dur = super().prefill(req, chunk, pool, ndev)
        t1 = log.now()
        if dur:
            log.calls.append(("prefill", t0, t1, (req.rid,),
                              (self.prompt_len,)))
            log.prefill_start[req.rid] = t0
            log.tokens[req.rid].append(t1)
        return dur

    def decode(self, batch, pool, ndev):
        log = self.log
        # positions each sequence attends over, the new one included
        lens = tuple(self.prompt_len + r.generated for r in batch)
        t0 = log.now()
        with log.span("executor.decode"):
            dur = super().decode(batch, pool, ndev)
        t1 = log.now()
        log.calls.append(("decode", t0, t1, tuple(r.rid for r in batch),
                          lens))
        for r in batch:
            log.tokens[r.rid].append(t1)
        return dur

    def reset(self):
        """Drop every request's cache and tokens."""
        self.state.clear()
        self.tokens.clear()
        self._finite.clear()


@dataclasses.dataclass
class Run:
    """What a metric reader gets: the cell's files, the harness's records
    and, in a traced run, the reduced trace."""
    cell: str
    cfg: dict
    mix: dict
    seconds: float
    due: dict                  # rid -> due time (ms), every request due
    max_new: dict              # rid -> tokens it asks for
    log: Log
    counts: object             # work counts of the configuration
    peaks: dict
    setup_s: float = 0.0
    trace: object = None       # trace.Reduced in a --trace 1 run
    trace_on_ms: float = 0.0
    trace_off_ms: float = 0.0

    @property
    def window_ms(self) -> float:
        return self.seconds * 1e3

    def traced_calls(self, kind: str) -> list:
        """The executor calls of ``kind`` made while the profiler ran."""
        return [c for c in self.log.calls if c[0] == kind
                and self.trace_on_ms <= c[1] and c[2] <= self.trace_off_ms]

    def in_system_ms(self) -> list:
        """Per request due in the window: (due, time its last token came
        back, or the window's end)."""
        out = []
        for rid, due in self.due.items():
            toks = self.log.tokens.get(rid, [])
            done = len(toks) >= self.max_new[rid]
            out.append((due, toks[-1] if done else self.window_ms))
        return out


def percentile(values, q: float):
    return float(np.percentile(values, q)) if len(values) else None


def itl_ms(run: Run) -> list:
    """Every gap between consecutive tokens of a request that ends in the
    window."""
    w = run.window_ms
    return [b - a for toks in run.log.tokens.values()
            for a, b in zip(toks, toks[1:]) if b <= w]


# ----------------------------------------------------------------- set-up


class Session:
    """A cell's set-up in this process: the model, the benchmark's
    weights, the compiled executor, and the reference checker."""

    def __init__(self, root: Path, spec: dict, cell: str, seed: int, device,
                 annotate: bool = False):
        self.root = root
        self.cell = find(spec["workloads"], cell, "workload")
        conf = find(spec["configs"], self.cell["config"], "configuration")
        self.cfg = load_json(root / conf["file"])
        self.mix = load_json(root / "bench" / "traffic"
                             / f"{self.cell['traffic']}.json")
        self.device = device
        self.arch = program_arch(self.cfg)
        self.P = int(self.mix["prompt_tokens"])
        self.M = traffic.max_output(self.mix)
        self.model = build_model(self.arch, no_dist())
        self.log = Log(annotate)
        self.seed = seed
        self.ex = BenchExecutor(self.model, make_weights(self.model, seed,
                                                         device),
                                self.arch.vocab, self.P, self.P + self.M,
                                device, seed, self.log)
        self.ex.compile()
        self.counts = reference.model_module(self.cfg).counts(self.cfg)

    def set_seed(self, seed: int):
        """New weights and prompts for ``seed``, on the compiled steps."""
        self.ex.reset()
        self.ex.params = None
        gc.collect()
        self.ex.params = make_weights(self.model, seed, self.device)
        self.ex.seed = self.seed = seed

    def engine(self) -> Engine:
        return Engine(Topology.serving(n_devices=2, prefill_devices=1),
                      SpecializedPolicy(),
                      cfg=ServeConfig(prefill_chunk=self.P,
                                      decode_batch_max=DECODE_BATCH_MAX,
                                      freq=engine_freq_config(
                                          self.cfg["registry"])),
                      executor=self.ex)

    # ------------------------------------------------------------ window

    def drive(self, seconds: float, mix: dict = None, trace_dir=None) -> dict:
        """Serve the mix for ``seconds`` from now on the wall clock.
        Returns the accounting of the requests due in the window."""
        mix = mix or self.mix
        log = self.log
        log.calls.clear()
        log.tokens.clear()
        log.prefill_start.clear()
        self.ex.reset()
        eng = self.engine()
        window_ms = seconds * 1e3
        heap, seq = [], itertools.count()
        due, max_new = {}, {}

        def push(_eng, t, kind, payload):
            heapq.heappush(heap, (t, next(seq), kind, payload))

        def request(rid, t, n):
            due[rid], max_new[rid] = t, n
            return Request(rid=rid, arrive_ms=t, prompt_len=self.P, max_new=n)

        first = [request(a.rid, a.due_ms, a.max_new)
                 for a in traffic.open_loop(mix, seconds)]
        eng.begin_run(first, push=push)

        trace_on, trace_off = None, None
        if trace_dir is not None:
            trace_on = max(0.0, (seconds - TRACE_SECONDS) / 2) * 1e3
            trace_off = trace_on + min(TRACE_SECONDS, seconds) * 1e3
        tracing, marker = False, None
        log.open()
        while heap:
            t, _, kind, payload = heap[0]
            now = log.now()
            if t >= window_ms or now >= window_ms:
                break
            if trace_on is not None and not tracing and now >= trace_on:
                jax.profiler.start_trace(str(trace_dir))
                marker = jax.profiler.TraceAnnotation("bench.traced")
                marker.__enter__()
                self.trace_on_ms, tracing = log.now(), True
            elif tracing and now >= trace_off:
                self.trace_off_ms = log.now()
                marker.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing, trace_on = False, None
            if t > now:
                # wait for the event, or for the traced window to open or
                # close on time
                wake = min(t, window_ms,
                           trace_off if tracing else trace_on or window_ms)
                with log.span("wait.arrival" if kind == "arrive"
                              else "wait.event"):
                    _sleep_until(log, wake)
                continue
            heapq.heappop(heap)
            with log.span("engine." + kind):
                eng.handle(max(t, now), kind, payload)
        if tracing:
            self.trace_off_ms = log.now()
            marker.__exit__(None, None, None)
            jax.profiler.stop_trace()

        # requests due in the window that the engine or the harness still
        # holds: queued, decoding, in a handoff, or not yet admitted
        pending = sum(1 for t, _, kind, _ in heap
                      if kind == "arrive" and t < window_ms)
        held = eng.queue_depth() + pending
        done = {rid for rid in due
                if len(log.tokens.get(rid, ())) >= max_new[rid]}
        self.due, self.max_new = due, max_new
        return {"attempted": len(due), "completed": len(done),
                "in_flight": held, "done": done}

    # ------------------------------------------------------------- check

    def accounting_errors(self, acct: dict) -> int:
        """Requests due in the window that are neither completed nor in
        flight, plus requests whose served tokens disagree with what they
        asked for or with what the harness saw come back."""
        errors = acct["attempted"] - acct["completed"] - acct["in_flight"]
        errors = abs(errors)
        for rid, n in self.max_new.items():
            served = len(self.ex.tokens.get(rid, ()))
            seen = len(self.log.tokens.get(rid, ()))
            if served != seen or served > n \
                    or (rid in acct["done"] and served != n):
                errors += 1
        return errors

    def served(self, rids) -> dict:
        return {rid: [int(t) for t in np.asarray(jax.device_get(
            self.ex.tokens[rid])).reshape(-1)] for rid in rids}

    def checker(self):
        if not hasattr(self, "_checker"):
            self._checker = reference.Checker(self.cfg, self.P, self.M)
        return self._checker

    def gaps(self, acct: dict, control: bool = False) -> dict:
        """Gaps of the served tokens over a sample of the completed
        requests: the widest (``max``), the mean over the tokens
        compared (``mean``) and their number (``n``); with ``control``
        also those of each control's tokens (``int8_max``, ``fp8_mean``,
        ...)."""
        rids = reference.sample({r: self.max_new[r] for r in acct["done"]},
                                self.seed, self.cfg["check"]["min_tokens"])
        served = self.served(rids)
        self.ex.state.clear()
        gc.collect()
        chk, w = self.checker(), self.ex.params
        got, ctl = [], defaultdict(list)
        for rid in rids:
            prompt = traffic.prompt(self.seed, rid, self.P, self.arch.vocab)
            if control:
                g, c = chk.control_gaps(w, prompt, served[rid])
                for name, v in c.items():
                    ctl[name].append(v)
            else:
                g = chk.program_gaps(w, prompt, served[rid])
            got.append(g)
        out = _summary(got, "")
        for name, v in ctl.items():
            out.update(_summary(v, name + "_"))
        return out


def gap_checks(limits: dict, g: dict, prefix: str = "") -> dict:
    """The gap numbers compared, each beside its limit (the
    configuration's ``check``), of the readings ``Session.gaps`` returns:
    the served tokens' by default, a control's with ``prefix``
    (``"fp8_"``)."""
    return {"none_compared": {"value": int(g[prefix + "n"] == 0), "limit": 0},
            **{f"{k}_gap_std": {"value": g[prefix + k],
                                "limit": limits[f"{k}_gap_std"]}
               for k in ("max", "mean")}}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _summary(gaps: list, prefix: str) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {prefix + "max": float(g.max()) if g.size else 0.0,
            prefix + "mean": float(g.mean()) if g.size else 0.0,
            prefix + "n": int(g.size)}


def _sleep_until(log: Log, t_ms: float):
    """Wait until ``t_ms`` on the harness's clock: sleep, then spin the
    last millisecond."""
    left = t_ms - log.now()
    if left > 1.5:
        time.sleep((left - 1.0) / 1e3)
    while log.now() < t_ms:
        pass


# -------------------------------------------------------------------- run


def run_cell(root: Path, spec: dict, cell: str, seed: int, seconds: float,
             traced: bool, t_start: float, device, peaks: dict) -> dict:
    """One run of ``cell``: the result line's fields, and the numbers
    compared with their limits under ``checks``. ``t_start`` is the
    process's start on ``time.perf_counter``; ``peaks`` are the device's
    (``peaks_for``)."""
    s = Session(root, spec, cell, seed, device, annotate=traced)
    trace_dir = root / ".bench_trace" if traced else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    acct = s.drive(seconds, trace_dir=trace_dir)
    # the CPU reports no memory statistics
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:s.cell["chips"]])

    run = Run(cell=cell, cfg=s.cfg, mix=s.mix, seconds=seconds, due=s.due,
              max_new=s.max_new, log=s.log, counts=s.counts,
              peaks=peaks, setup_s=setup_s)
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    breakdown = None
    if traced:
        run.trace_on_ms, run.trace_off_ms = s.trace_on_ms, s.trace_off_ms
        run.trace = trace.reduce(trace.load(trace_dir), s.trace_on_ms,
                                 s.trace_off_ms)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device_info["busy_s"] = run.trace.busy_s
        device_info["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()

    checks = {
        "accounting_errors": {"value": s.accounting_errors(acct), "limit": 0},
        "nonfinite_steps": {"value": 0 if s.ex.all_finite() else 1,
                            "limit": 0},
    }
    checks.update(gap_checks(s.cfg["check"], s.gaps(acct)))
    correct = passed(checks)

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, cell, kind):
        v = load_metric(root, m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": acct["attempted"],
           "failed": max(0, acct["attempted"] - acct["completed"]
                         - acct["in_flight"]),
           "metrics": metrics, "device": device_info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
