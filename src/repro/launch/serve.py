"""Serving driver: a real model behind the specialization engine.

Compiles a model's prefill and decode at its published width (or the
CPU-sized config with ``--reduced``) into a ``RealModelExecutor`` on one
device, and serves requests through the event-driven engine
(`repro.sched.engine`): the ``SpecializedPolicy`` runs prefill, the heavy
work, on the prefill pool of a two-pool ``Topology`` and decode on the
other, and each step's service time is the measured duration of its
device call. The engine's frequency domain takes the license levels
calibrated for the arch in ``analysis/derived.json`` where there are any.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \\
      --requests 16 --prompt 64 --max-new 16 --reduced

``--mode cluster`` runs N one-device engine shards behind the
frequency-aware router (`repro.sched.cluster`): shard ``i`` holds its
own copy of the parameters, its caches and its prompts on
``jax.devices()[i]``, and ``--fault-plan`` injects a registered fault
plan. Either mode prints the requests completed and their latency
percentiles, and ``main`` returns a ``ServeRun``.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.analysis import derived
from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import PER_ROW_CACHE_FAMILIES, build_model
from repro.sched import (ClusterConfig, ClusterEngine, ClusterTopology,
                         SpecializedPolicy, Topology)
from repro.sched.engine import Engine, Request, ServeConfig
from repro.sched.workload import load_trace


def _greedy(logits):
    """Next token [B,1] and whether every logit of the step is finite."""
    return (jnp.argmax(logits, -1).astype(jnp.int32)[:, None],
            jnp.isfinite(logits).all())


def serve_steps(model, max_seq: int):
    """The two step functions the executor compiles, each returning
    ``(next_token, all_logits_finite, cache, lengths)``:
    ``prefill(params, tokens)`` fills a fresh ``max_seq`` cache, and
    ``decode(params, cache, token, lengths)`` appends one token. Decode
    takes one cache of a batch with tokens ``[B,1]`` and lengths ``[B]``,
    or per-row sequences of caches, ``[1,1]`` tokens and ``[1]`` lengths,
    which it returns as such: the rows advance in one step that reads the
    weights once, each over its own cache."""
    def prefill(p, toks):
        cache = model.init_cache(p, {"tokens": toks}, toks.shape[0],
                                 max_seq)
        logits, cache = model.prefill(p, {"tokens": toks}, cache)
        return (*_greedy(logits), cache,
                jnp.full((toks.shape[0],), toks.shape[1], jnp.int32))

    def decode(p, cache, tok, lengths):
        rows = not isinstance(cache, dict)
        if rows:
            tok, lengths = jnp.concatenate(tok), jnp.concatenate(lengths)
        logits, cache = model.decode_step(p, cache, tok, lengths)
        tok, ok = _greedy(logits)
        lengths = lengths + 1
        if rows:
            tok, lengths = (tuple(a[i:i + 1] for i in range(a.shape[0]))
                            for a in (tok, lengths))
        return tok, ok, cache, lengths

    return prefill, decode


def jit_steps(steps):
    """``serve_steps``' two functions jitted as the executor runs them:
    decode donates its caches (argument 1), which it then updates in place
    instead of copying. A donated cache is never read again."""
    prefill, decode = steps
    return jax.jit(prefill), jax.jit(decode, donate_argnums=1)


def round_sizes(cap: int) -> tuple:
    """The sizes the executor compiles a decode round for: the powers of
    two below ``cap``, and ``cap``."""
    return tuple(1 << i for i in range((cap - 1).bit_length())) + (cap,)


def placed(s, sharding):
    """``s``'s shape and dtype, placed on ``sharding``."""
    return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)


def _call(fn, *args):
    """``fn(*args)``, a compiled step, once its tokens are ready: the call
    annotated ``executor.dispatch``, the wait ``executor.sync``."""
    with jax.profiler.TraceAnnotation("executor.dispatch"):
        out = fn(*args)
    with jax.profiler.TraceAnnotation("executor.sync"):
        jax.block_until_ready(out[0])
    return out


class RealModelExecutor:
    """Engine executor that runs real jitted prefill/decode steps on one
    device.

    The engine calls ``prefill``/``decode`` when its schedule says so;
    we execute the actual computation and return the measured wall-clock
    duration in ms, which becomes the simulated service time. The
    parameters, the per-request KV caches (keyed by request id) and the
    prompts all live on ``device`` — the handoff the engine charges
    between pools corresponds to moving one of these caches. Request
    ``rid``'s prompt is drawn from ``(seed, rid)``, so it does not depend
    on which executor serves it or in what order. Every emitted token is
    recorded in ``tokens[rid]``.

    Each prompt upload, step dispatch and wait for a step's token is a
    ``jax.profiler.TraceAnnotation`` (``executor.upload``,
    ``executor.dispatch``, ``executor.sync``): while the profiler runs,
    the host's time in a call shows on the trace's host plane, beside
    the device's programs.

    A decode round is one device call: the round's requests advance in
    one program that reads the weights once, each over its own cache.
    The program is compiled for each of ``round_sizes(batch)``; a round
    runs the smallest that holds it, its spare rows filled with scratch
    caches that the executor owns, whose outputs are dropped. Decode
    donates the caches it is given: ``decode`` pops each request's state
    before the call and keeps only the cache the call returns.
    """

    def __init__(self, model, params, vocab: int, prompt_len: int,
                 max_seq: int, device, seed: int = 0, batch: int = 8):
        if model.family not in PER_ROW_CACHE_FAMILIES:
            raise ValueError(
                f"{model.family!r} models decode one cache of a batch; the "
                "executor decodes a round over per-request caches, which "
                f"only {sorted(PER_ROW_CACHE_FAMILIES)} models take")
        self.vocab = vocab
        self.prompt_len = prompt_len
        self.seed = seed
        self.device = device
        self.params = jax.device_put(params, device)
        self.state = {}          # rid -> (cache, last_tok, lengths)
        self.tokens = {}         # rid -> emitted tokens, device [1,1] each
        self._finite = []        # per step: every logit finite (device)
        self._steps = serve_steps(model, max_seq)
        self.sizes = round_sizes(batch)
        self.prefill_j = None
        self.decode_j = {}       # round size -> compiled decode
        self._spare = []         # scratch caches that pad a round
        self._spare_row = None   # the token and length of a spare row

    def compile(self) -> float:
        """Compile prefill, and decode for each round size, for this
        device, and run each once, ahead of the run: no compile or
        first-call cost lands in a measured service time. Returns the
        seconds it took."""
        t0 = time.perf_counter()
        prefill, decode = jit_steps(self._steps)
        on = SingleDeviceSharding(self.device)
        toks = jax.ShapeDtypeStruct((1, self.prompt_len), jnp.int32,
                                    sharding=on)
        self.prefill_j = prefill.lower(self.params, toks).compile()
        # the device runs prefill's first call while the host compiles
        # the rounds
        first = self.prefill_j(self.params, jax.device_put(
            np.zeros(toks.shape, np.int32), self.device))
        tok, _, cache, lengths = jax.tree.map(
            lambda s: placed(s, on), jax.eval_shape(prefill, self.params,
                                                    toks))
        self.decode_j = {b: decode.lower(self.params, (cache,) * b,
                                         (tok,) * b, (lengths,) * b).compile()
                         for b in self.sizes}
        tok, _, _, lengths = first
        self._spare_row = (tok, lengths)
        caches = [jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype,
                                                   device=on), cache)
                  for _ in range(self.sizes[-1])]
        for b in self.sizes:
            caches[:b] = self.decode_j[b](self.params, tuple(caches[:b]),
                                          (tok,) * b, (lengths,) * b)[2]
        jax.block_until_ready(caches)
        pads = max(self._size(n) - n for n in range(1, self.sizes[-1] + 1))
        self._spare = caches[:pads]
        return time.perf_counter() - t0

    def _size(self, n: int) -> int:
        """The compiled round size that holds ``n`` sequences."""
        for b in self.sizes:
            if b >= n:
                return b
        raise ValueError(f"a round of {n} sequences exceeds the executor's "
                         f"largest, {self.sizes[-1]}")

    def prompt(self, rid: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, rid))
        return rng.integers(0, self.vocab, size=(1, self.prompt_len),
                            dtype=np.int32)

    def prefill(self, req: Request, chunk: int, pool: str,
                ndev: int) -> float:
        # the jitted prefill is not chunkable: the whole prompt runs (and
        # is charged) on the first chunk call; later chunk calls for the
        # same request are free — total charged time stays the real cost
        if req.rid in self.state:
            return 0.0
        with jax.profiler.TraceAnnotation("executor.upload"):
            toks = jax.device_put(self.prompt(req.rid), self.device)
        t0 = time.perf_counter()
        tok, ok, cache, lengths = _call(self.prefill_j, self.params, toks)
        dur_ms = (time.perf_counter() - t0) * 1e3
        self.state[req.rid] = (cache, tok, lengths)
        self.tokens[req.rid] = [tok]
        self._finite.append(ok)
        return dur_ms

    def decode(self, batch, pool: str, ndev: int) -> float:
        t0 = time.perf_counter()
        n = len(batch)
        if not n:
            return 0.0
        pad = self._size(n) - n
        states = [self.state.pop(r.rid) for r in batch]
        states += [(c, *self._spare_row) for c in self._spare[:pad]]
        caches, toks, lengths = zip(*states)
        toks, ok, caches, lengths = _call(self.decode_j[n + pad],
                                          self.params, caches, toks, lengths)
        self._spare[:pad] = caches[n:]
        self._finite.append(ok)
        for req, cache, tok, length in zip(batch, caches, toks, lengths):
            self.tokens[req.rid].append(tok)
            # a request that finishes with this token drops its KV cache,
            # so executor memory scales with concurrency, not total served
            if req.generated + 1 < req.max_new:
                self.state[req.rid] = (cache, tok, length)
        return (time.perf_counter() - t0) * 1e3

    def emitted(self) -> dict:
        """rid -> the greedy tokens this executor emitted, as ints."""
        return {rid: [int(t[0, 0]) for t in jax.device_get(toks)]
                for rid, toks in self.tokens.items()}

    def all_finite(self) -> bool:
        """Whether every logit of every step run so far was finite."""
        return bool(np.all(jax.device_get(self._finite)))


@dataclasses.dataclass
class ServeRun:
    """What ``main`` returns: the run's metrics, the executors by name
    (one per engine shard) and the seconds spent compiling before the
    measured window."""
    metrics: object
    executors: dict
    compile_s: float


def engine_freq_config(arch: str):
    """The engine's ms-base frequency domain, with the license levels
    the calibration derived for this arch (falls back to the hand-tuned
    ``ENGINE_FREQ_MS`` levels for uncalibrated archs)."""
    from repro.sched.freq import ENGINE_FREQ_MS
    if arch in derived.workloads():
        return dataclasses.replace(
            ENGINE_FREQ_MS,
            freqs_ghz=tuple(derived.freq_levels_ghz(arch)))
    return ENGINE_FREQ_MS


def _requests(args) -> list:
    """The run's requests: a replayed trace (``--workload``) or
    fixed-interval arrivals at ``--rate``. Token counts are clamped to
    the jitted model's fixed prompt/max-new dims (the executor runs
    whole prompts)."""
    P, N = args.prompt, args.max_new
    if not args.workload:
        interval_ms = 1000.0 / args.rate
        return [Request(rid=i, arrive_ms=i * interval_ms, prompt_len=P,
                        max_new=N) for i in range(args.requests)]
    # scenario name or JSON trace path (repro.sched.workload): the trace
    # supplies arrival times, tenants and per-tenant deadline windows
    trace = load_trace(args.workload, seed=args.seed)
    reqs = [Request(rid=r.rid, arrive_ms=r.arrive_ms, prompt_len=P,
                    max_new=N, tenant=r.tenant,
                    deadline_window_ms=r.deadline_window_ms)
            for r in trace.requests[:args.requests]]
    print(f"[serve] workload {args.workload!r}: {len(reqs)} requests "
          f"replayed (of {len(trace.requests)} in the trace)")
    return reqs


def _executors(args, cfg, model, params, devices: dict) -> tuple:
    """One executor per device of ``devices`` (name -> device), each
    compiled and warmed for the run's prompt, length and batch. Returns
    the executors by name and the seconds their compiling took."""
    P = args.prompt
    executors = {name: RealModelExecutor(model, params, cfg.vocab, P,
                                         P + args.max_new, dev,
                                         seed=args.seed, batch=args.batch)
                 for name, dev in devices.items()}
    compile_s = sum(ex.compile() for ex in executors.values())
    print(f"[serve] compiled and warmed prefill+decode for "
          f"{len(executors)} executor(s) in {compile_s:.1f}s (set-up, "
          "outside the measured window)")
    return executors, compile_s


def _serve_config(args) -> ServeConfig:
    """Each engine's config: whole prompts in one chunk, decode rounds
    of at most ``--batch``, and the arch's frequency domain."""
    return ServeConfig(prefill_chunk=args.prompt,
                       decode_batch_max=args.batch,
                       freq=engine_freq_config(args.arch))


def _print_latency(s: dict, extra: str = ""):
    print(f"[serve] ttft_p50={s['ttft_p50_ms']:.1f}ms "
          f"ttft_p99={s['ttft_p99_ms']:.1f}ms "
          f"itl_p50={s['itl_p50_ms']:.1f}ms "
          f"itl_p99={s['itl_p99_ms']:.1f}ms{extra}")


def run_engine(args, cfg, model, params) -> ServeRun:
    """Real-model serving through the Policy/Topology engine on the
    first local device."""
    N = args.max_new
    executors, compile_s = _executors(args, cfg, model, params,
                                      {"engine": jax.devices()[0]})
    reqs = _requests(args)
    eng = Engine(Topology.serving(n_devices=2, prefill_devices=1),
                 SpecializedPolicy(), cfg=_serve_config(args),
                 executor=executors["engine"])
    t0 = time.perf_counter()
    m = eng.run(reqs)               # no horizon: run to completion
    wall = time.perf_counter() - t0
    s = m.summary()
    print(f"[serve] {m.completed}/{len(reqs)} requests, "
          f"{m.completed * N} tokens in {wall:.1f}s wall")
    _print_latency(s)
    busy = ", ".join(
        "{}: heavy={:.0f}ms light={:.0f}ms".format(k, v["heavy"], v["light"])
        for k, v in m.pool_busy.items())
    print(f"[serve] handoffs={s['handoffs']} steals={s['steals']} "
          f"pool_busy={{{busy}}}")
    return ServeRun(m, executors, compile_s)


def shard_devices(n_shards: int) -> list:
    """One local device per engine shard: shard ``i`` runs on
    ``jax.devices()[i]``. Refuses more shards than devices rather than
    stacking two shards on one device."""
    devs = jax.devices()
    if not 1 <= n_shards <= len(devs):
        raise ValueError(f"{n_shards} shards need as many devices; "
                         f"{len(devs)} available")
    return devs[:n_shards]


def run_cluster(args, cfg, model, params) -> ServeRun:
    """Real-model cluster serving: N shards, each a two-pool engine
    with its own jitted executor on its own device, behind the
    SLO-aware router."""
    print(f"[serve] {args.shards}-shard cluster under "
          f"{args.cluster_policy!r}")
    cluster = ClusterTopology.homogeneous(args.shards, 2, 1)
    devices = {}
    for spec, dev in zip(cluster.shards, shard_devices(args.shards)):
        devices[spec.name] = dev
        print(f"[serve] {spec.name}: {spec.topology.n_units} pool units "
              f"on {dev.platform}:{dev.id}")
    executors, compile_s = _executors(args, cfg, model, params, devices)
    reqs = _requests(args)
    ccfg = ClusterConfig(serve=_serve_config(args))
    eng = ClusterEngine(cluster, args.cluster_policy, cfg=ccfg,
                        executors=executors)
    plan = None
    if args.fault_plan:
        from repro.sched.faults import resolve_fault_plan
        plan = resolve_fault_plan(args.fault_plan)
        print(f"[serve] fault plan {plan.name!r} "
              f"(hash {plan.plan_hash})")
    t0 = time.perf_counter()
    if plan is None:
        m = eng.run(reqs)           # no horizon: run to completion
    else:
        # fault injection needs a finite horizon: faults stop with the
        # arrival window, the drain tail lets recovery/retries settle
        last_arrive = max(r.arrive_ms for r in reqs) if reqs else 0.0
        m = eng.run(reqs, last_arrive + 60_000.0, fault_plan=plan,
                    fault_horizon_ms=last_arrive)
    wall = time.perf_counter() - t0
    s = m.summary()
    print(f"[serve] {s['completed']}/{len(reqs)} requests in "
          f"{wall:.1f}s wall")
    _print_latency(s, f" holds={s['router_holds']}")
    if plan is not None:
        print(f"[serve] faults: injected={s['faults_injected']} "
              f"recoveries={s['shard_recoveries']} "
              f"drained={s['drained']} retries={s['retries']} "
              f"dropped={s['dropped']} shed={s['shed_total']} "
              f"expired={s['expired_total']}")
    for name, sh in m.shard_summaries().items():
        print(f"[serve]   {name}: routed={sh['routed']} "
              f"done={sh['completed']}")
    return ServeRun(m, executors, compile_s)


def main(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU runs); "
                         "default: the published width")
    ap.add_argument("--mode", choices=("engine", "cluster"),
                    default="engine")
    ap.add_argument("--shards", type=int, default=2,
                    help="cluster mode: number of engine shards, one "
                         "device each")
    ap.add_argument("--cluster-policy", default="cluster-adaptive",
                    help="cluster mode: registered cluster policy "
                         "(cluster-rr, cluster-queue, cluster-freq, "
                         "cluster-adaptive)")
    ap.add_argument("--fault-plan", default=None,
                    help="cluster mode: registered fault plan to "
                         "inject (crash, brownout, straggler, flaky, "
                         "storm, ... — see repro.sched.faults)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="request arrival rate (req/s of engine time)")
    ap.add_argument("--workload", default=None,
                    help="arrival pattern: a registered scenario name "
                         "(steady, bursty, diurnal, heavy_tail, "
                         "multi_tenant) or a path to a JSON trace; "
                         "default: fixed-interval arrivals at --rate")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}, {cfg.param_dtype}")
    model = build_model(cfg, no_dist())
    params = jax.jit(model.init)(jax.random.key(args.seed))
    run = run_cluster if args.mode == "cluster" else run_engine
    return run(args, cfg, model, params)


if __name__ == "__main__":
    main()
