"""Bring-up check: serve qwen1.5-0.5b at its published width on a TPU.

  python chip_smoke.py             # one chip: engine mode, 8 requests
  python chip_smoke.py --chips 4   # four one-chip shards vs one shard

One chip: ``repro.launch.serve.main`` in engine mode, 8 requests of a
1024-token prompt and 64 new tokens, decode batch 4, random weights from
seed 0. It checks that every request completed, that every logit was
finite, and that each greedy token of request 0 is the top token of a
float32 teacher-forced forward pass, up to a tenth of that row's logit
spread. ``--chips 4`` runs only the cluster path: four shards, one per
chip, and the same requests through one shard; it checks that every
request completed in both, that the shards sit on four distinct devices
and that every request's greedy tokens agree between the two runs.

Everything runs in this one process. Without a TPU it exits non-zero and
prints no result. Times printed here are from one bring-up run, not a
benchmark. The last line of a passing run is one JSON object naming the
device.
"""
import argparse
import dataclasses
import json
import os
import sys
from importlib import metadata

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
ARCH = "qwen1.5-0.5b"
PROMPT, MAX_NEW, REQUESTS = 1024, 64, 8
SERVE_ARGS = ["--arch", ARCH, "--requests", str(REQUESTS),
              "--prompt", str(PROMPT), "--max-new", str(MAX_NEW),
              "--batch", "4", "--seed", "0"]
# a served token may trail the reference's top logit by this share of
# the row's standard deviation (bf16 serving against a float32 reference)
REF_GAP_TOL = 0.1
# the cluster comparison spaces arrivals 1 s apart in engine time, longer
# than one request's service, so the single shard is never saturated: the
# router would otherwise let held requests expire at their 50 ms deadline
# window, and both runs must serve every request for their tokens to be
# compared
CLUSTER_ARGS = ["--mode", "cluster", "--rate", "1"]


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def reference_gaps(cfg, params, prompt, served):
    """How far below the top logit each served token sits in a float32
    teacher-forced forward over ``prompt + served``, in units of that
    row's standard deviation; and whether it is the top token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import lm_forward

    f32 = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    seq = np.concatenate([prompt[0], served[:-1]]).astype(np.int32)[None]

    @jax.jit
    def gaps(p, seq, served):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        logits = lm_forward(p, seq, f32)[0][0, prompt.shape[1] - 1:]
        chosen = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
        return ((logits.max(-1) - chosen) / logits.std(-1),
                logits.argmax(-1) == served)

    gap, top = jax.device_get(gaps(params, seq, np.asarray(served)))
    return gap, top


def one_chip(serve, cfg):
    import jax

    run = serve.main(SERVE_ARGS + ["--mode", "engine"])
    s = run.metrics.summary()
    ex = run.executors["engine"]
    log(f"compile (set-up, outside the measured window): "
        f"{run.compile_s:.2f} s")
    log(f"one bring-up run on {jax.devices()[0].device_kind}, not a "
        f"benchmark: ttft p50 {s['ttft_p50_ms']:.2f} ms, "
        f"p99 {s['ttft_p99_ms']:.2f} ms; itl p50 {s['itl_p50_ms']:.2f} ms, "
        f"p99 {s['itl_p99_ms']:.2f} ms")
    check(s["completed"] == REQUESTS,
          f"{s['completed']}/{REQUESTS} requests completed")
    check(ex.all_finite(), "a served logit was not finite")
    tokens = ex.emitted()
    check(sorted(tokens) == list(range(REQUESTS))
          and all(len(t) == MAX_NEW for t in tokens.values()),
          f"emitted token counts {({r: len(t) for r, t in tokens.items()})}")
    log(f"{s['completed']}/{REQUESTS} requests completed, "
        f"{MAX_NEW} tokens each, every logit finite")

    gap, top = reference_gaps(cfg, ex.params, ex.prompt(0), tokens[0])
    log(f"request 0 against the float32 reference: top token at "
        f"{int(top.sum())}/{len(top)} steps, largest gap "
        f"{float(gap.max()):.4f} std (limit {REF_GAP_TOL})")
    check(float(gap.max()) <= REF_GAP_TOL,
          f"served tokens trail the reference by up to {gap.max():.4f} std")


def four_chips(serve, cfg):
    import jax

    four = serve.main(SERVE_ARGS + CLUSTER_ARGS + ["--shards", "4"])
    ids = []
    for name, ex in four.executors.items():
        held = {d.id for t in (ex.params, ex.tokens)
                for leaf in jax.tree.leaves(t)
                for d in leaf.devices()}
        check(held == {ex.device.id},
              f"{name} holds arrays on devices {sorted(held)}, "
              f"not only on {ex.device.id}")
        ids.append(ex.device.id)
    log(f"shard device ids: {ids}")
    check(len(set(ids)) == 4, f"shards share devices: {ids}")
    tokens4 = {}
    for ex in four.executors.values():
        tokens4.update(ex.emitted())
    s4 = four.metrics.summary()
    del four

    one = serve.main(SERVE_ARGS + CLUSTER_ARGS + ["--shards", "1"])
    s1 = one.metrics.summary()
    tokens1 = one.executors["shard0"].emitted()
    for label, s in (("4 shards", s4), ("1 shard", s1)):
        log(f"{label}: {s['completed']}/{REQUESTS} completed, "
            f"{s['expired_total']} expired at the router; one bring-up "
            f"run, not a benchmark: ttft p50 {s['ttft_p50_ms']:.2f} ms, "
            f"itl p50 {s['itl_p50_ms']:.2f} ms")
        check(s["completed"] == REQUESTS,
              f"{label}: {s['completed']}/{REQUESTS} requests completed")
    check(sorted(tokens4) == sorted(tokens1) == list(range(REQUESTS)),
          f"requests served: {sorted(tokens4)} vs {sorted(tokens1)}")
    differ = [rid for rid in tokens1 if tokens4[rid] != tokens1[rid]]
    check(not differ, f"greedy tokens differ for requests {differ}")
    log(f"greedy tokens identical for all {REQUESTS} requests "
        f"({MAX_NEW} each)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-shard cluster comparison")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"FAIL: no TPU (JAX found {devs[0].platform})")
        return 1
    if len(devs) < args.chips:
        log(f"FAIL: {args.chips} chips requested, {len(devs)} found")
        return 1
    sys.path.insert(0, SRC)
    from repro.configs import get_arch
    from repro.launch import serve

    log(f"jax {jax.__version__}, jaxlib {metadata.version('jaxlib')}, "
        f"libtpu {metadata.version('libtpu')}")
    log(f"device {devs[0].device_kind}, count {len(devs)}")
    cfg = get_arch(ARCH)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}, {cfg.param_dtype}")
    try:
        (four_chips if args.chips == 4 else one_chip)(serve, cfg)
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    for d in devs[:args.chips]:
        log(f"device {d.id} peak_bytes_in_use "
            f"{d.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
