"""Calibration backend: run the region pass over the kernel suite and
the model zoo, derive per-workload heavy tags, FrequencyDomain level
configs and scenario parameters, and write the committed
``derived.json`` artifact.

  PYTHONPATH=src python -m repro.analysis.calibrate            # table
  PYTHONPATH=src python -m repro.analysis.calibrate --update   # rewrite

Everything downstream consumes the artifact through
:mod:`repro.analysis.derived`: ``sched.workload`` registers one
``zoo/<arch>`` scenario per architecture, ``core.workloads.trace_tasks``
reads the per-scenario cycle scaling, and ``launch.serve`` uses the
derived engine frequency config.

Derivations (all documented here because the artifact is committed):

* **Heavy tags** — :func:`repro.analysis.regions.tag_heavy` over each
  workload's prefill/decode timelines (share + density criterion).

* **Frequency levels** — the Xeon Gold 6130 reference drops (2.8 ->
  2.4 -> 1.9 GHz, the paper's measured licenses) scaled by measured
  instruction density, mirroring the density-dependent throttling the
  paper describes. L1 scales with the heavy *time* share of the prefill
  timeline (every zoo prefill is fully vectorized, so f1 lands on the
  hardware-table 2.4 across the board); L2 applies the additional
  2.4 -> 1.9 drop scaled by the MXU *time* share against a 0.40
  reference density — the one quantity that genuinely separates the zoo
  (11% for a 0.5B dense model up to 37% for the VLM's fused image
  prefill), so elementwise-leaning models keep most of their L2 clock
  while MXU-saturated prefills drop to the paper's 1.9/2.8 ratio.

* **Scenario parameters** — per-family serving shapes (prompt/output
  distributions below) with the Poisson rate set so every scenario
  presents the same prefill-token load as the calibrated ``steady``
  operating point of the 16-device reference replay cell
  (rate x mean_prompt ~= 3.2/s x 2048 tok). The replay cell is fixed
  reference hardware; the model shapes the *workload*, not the cell.

* **Simulator cycle scaling** — per-token trace-replay costs scaled by
  the cube root of the workload's flops ratio to the reference arch
  (qwen1.5-0.5b), clamped to [0.5, 2.0]. The cube root compresses the
  zoo's three-orders-of-magnitude flops range into the band where the
  OS-simulator leg still drains inside the tier-1 horizon; the raw
  ratios are recorded alongside so nothing is hidden.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from repro.analysis.costs import CostConfig
from repro.analysis.differential import FLOPS_REL_TOL, differential
from repro.analysis.regions import (MachineModel, RegionTimeline, segment,
                                    tag_heavy)

DERIVED_PATH = Path(__file__).with_name("derived.json")

CALIB_PROMPT = 2048          # representative serving prompt (tokens)
REF_ARCH = "qwen1.5-0.5b"

# the reference replay cell's calibrated operating point (steady):
# 3.2 req/s x U(1024,3072) prompts — every derived scenario matches
# this prefill-token load so the matrix gates stay meaningful
TARGET_PREFILL_TOK_PER_S = 3.2 * 2048.0

# Xeon Gold 6130 license drops (paper tbl: 2.8 -> 2.4 -> 1.9 GHz)
F0_GHZ = 2.8
L1_DROP = 1.0 - 2.4 / 2.8       # 14.3%
L2_EXTRA_DROP = 1.0 - 1.9 / 2.4  # additional 20.8% below f1
FULL_DENSITY = 0.85             # heavy time share for the full L1 drop
MXU_REF_SHARE = 0.40            # MXU time share for the full L2 drop

# trace-replay cycle costs of the reference arch (core/workloads.py)
REF_PREFILL_CYCLES = 205.0
REF_DECODE_CYCLES = 6_000.0

# per-family serving shapes: (prompt dist, output dist) component dicts
# in sched.workload's registry format ({"kind": ..., **params})
FAMILY_PROFILES: Dict[str, Tuple[Dict, Dict]] = {
    # chat/code assistants: mid prompts, zipf-tailed generations
    "dense": ({"kind": "lognormal", "median": 1400.0, "sigma": 0.65,
               "lo": 256, "hi": 6144},
              {"kind": "zipf", "alpha": 1.5, "lo": 32, "hi": 224}),
    # early-fusion VLM: image-token prompts are long and tight
    "vlm": ({"kind": "lognormal", "median": 2400.0, "sigma": 0.45,
             "lo": 512, "hi": 8192},
            {"kind": "fixed", "n": 48}),
    # frontier MoE: long analytic prompts, fixed-ish generations
    "moe": ({"kind": "lognormal", "median": 2800.0, "sigma": 0.6,
             "lo": 512, "hi": 8192},
            {"kind": "fixed", "n": 64}),
    # sub-quadratic backbones serve the long-context tier
    "hybrid": ({"kind": "lognormal", "median": 3200.0, "sigma": 0.8,
                "lo": 512, "hi": 8192},
               {"kind": "uniform", "lo": 32, "hi": 96}),
    "ssm": ({"kind": "lognormal", "median": 3200.0, "sigma": 0.8,
             "lo": 512, "hi": 8192},
            {"kind": "uniform", "lo": 32, "hi": 96}),
    # speech-to-text: fixed encoder frames, uniform transcripts
    "audio": ({"kind": "fixed", "n": 1500},
              {"kind": "uniform", "lo": 48, "hi": 160}),
}

# reduced-config archs the static-vs-HLO differential compiles (CPU);
# three families so the oracle covers attention, GQA and recurrent paths
DIFFERENTIAL_ARCHS = ("qwen1.5-0.5b", "stablelm-12b", "rwkv6-3b")

# documented known divergences: interpret-mode pallas kernels lower
# through the jaxpr interpreter, so the compiled HLO measures the
# interpreter's scaffolding (bound-checked dynamic slices, rotate
# decomposed to shift/or chains) rather than the kernel's algorithmic
# flops — the static claim is the honest one there. Recorded in
# derived.json with agrees=false, reported in the table, but not a
# calibration failure.
KNOWN_DIVERGENT = {"chacha20"}


def _mean_len(dist: Dict) -> float:
    k = dist["kind"]
    if k == "fixed":
        return float(dist["n"])
    if k == "uniform":
        return (dist["lo"] + dist["hi"]) / 2.0
    if k == "lognormal":
        m = dist["median"] * math.exp(dist["sigma"] ** 2 / 2.0)
        return min(max(m, dist["lo"]), dist["hi"])
    if k == "zipf":
        return dist["lo"] + 12.0          # rough zipf(1.5) tail mean
    raise ValueError(k)


def _clamp(v: float, lo: float, hi: float) -> float:
    return min(max(v, lo), hi)


# ------------------------------------------------------------ timelines


def kernel_timelines(machine: MachineModel = MachineModel()
                     ) -> List[RegionTimeline]:
    """The pallas suite: chacha20 is the paper's SSL-library analogue
    (pure wide-vector, no MXU), the attention kernels the MXU class."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import (chacha20_keystream, flash_attention,
                                   flash_decode)

    key = jnp.zeros((8,), jnp.uint32)
    nonce = jnp.zeros((3,), jnp.uint32)
    q = jax.ShapeDtypeStruct((1, 8, 512, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 8, 1024, 64), jnp.float32)
    qd = jax.ShapeDtypeStruct((1, 8, 64), jnp.float32)
    lens = jax.ShapeDtypeStruct((1,), jnp.int32)
    return [
        segment(lambda k, n: chacha20_keystream(
            k, n, 1, n_blocks=256, tile=256, interpret=True),
            key, nonce, name="chacha20", machine=machine),
        segment(lambda a, b, c: flash_attention(a, b, c, interpret=True),
                q, q, q, name="flash_attention", machine=machine),
        segment(lambda a, b, c, l: flash_decode(a, b, c, l, interpret=True),
                qd, kv, kv, lens, name="flash_decode", machine=machine),
    ]


class _CalibShape:
    """Minimal ShapeConfig stand-in for model.input_specs."""

    def __init__(self, seq_len: int, kind: str):
        self.name = f"calib_{kind}"
        self.seq_len = seq_len
        self.global_batch = 1
        self.kind = kind


def model_timelines(arch: str, prompt: int = CALIB_PROMPT,
                    machine: MachineModel = MachineModel(),
                    cfg: CostConfig = CostConfig(),
                    reduced: bool = False) -> Dict[str, RegionTimeline]:
    """Abstract-trace one architecture's prefill and decode entrypoints
    at full (or ``reduced``) config — nothing is materialized."""
    import jax

    from repro.configs import get_arch
    from repro.dist.context import no_dist
    from repro.models.api import build_model

    acfg = get_arch(arch)
    if reduced:
        acfg = acfg.reduced()
    model = build_model(acfg, no_dist())
    params = model.abstract_params()
    max_seq = prompt + 128
    pre_in, _ = model.input_specs(_CalibShape(prompt, "prefill"))
    dec_in, _ = model.input_specs(_CalibShape(prompt, "decode"))

    def prefill(p, batch):
        cache = model.init_cache(p, batch, 1, max_seq)
        return model.prefill(p, batch, cache)

    cache = jax.eval_shape(
        lambda p, b: model.init_cache(p, b, 1, max_seq), params, pre_in)
    return {
        "prefill": segment(prefill, params, pre_in, name="prefill",
                           machine=machine, cfg=cfg),
        "decode_step": segment(
            lambda p, c, t, l: model.decode_step(p, c, t, l),
            params, cache, dec_in["tokens"], dec_in["lengths"],
            name="decode_step", machine=machine, cfg=cfg),
    }


# ------------------------------------------------------------ deriving


def derive_freq_levels(prefill: RegionTimeline) -> List[float]:
    """(f0, f1, f2) GHz from measured wide-vector densities (see module
    docstring). Strictly decreasing by construction."""
    heavy_time_share = prefill.heavy_share
    mxu_time_share = prefill.level_share(2)
    f1 = F0_GHZ * (1.0 - L1_DROP * _clamp(heavy_time_share / FULL_DENSITY,
                                          0.0, 1.0))
    f2 = f1 * (1.0 - L2_EXTRA_DROP * _clamp(mxu_time_share / MXU_REF_SHARE,
                                            0.0, 1.0))
    f1 = min(f1, F0_GHZ - 0.05)
    f2 = min(f2, f1 - 0.05)
    return [round(F0_GHZ, 3), round(f1, 3), round(f2, 3)]


def derive_scenario(family: str, prefill: RegionTimeline,
                    decode: RegionTimeline,
                    ref_prefill_flops_per_tok: float,
                    ref_decode_flops: float,
                    prompt: int = CALIB_PROMPT) -> Dict:
    prompt_dist, output_dist = FAMILY_PROFILES[family]
    rate = TARGET_PREFILL_TOK_PER_S / _mean_len(prompt_dist)
    pre_ratio = (prefill.flops / prompt) / ref_prefill_flops_per_tok \
        if ref_prefill_flops_per_tok else 1.0
    dec_ratio = decode.flops / ref_decode_flops if ref_decode_flops else 1.0
    pre_scale = _clamp(pre_ratio ** (1.0 / 3.0), 0.5, 2.0)
    dec_scale = _clamp(dec_ratio ** (1.0 / 3.0), 0.5, 2.0)
    return {
        "rate_per_s": round(rate, 3),
        "prompt": prompt_dist,
        "output": output_dist,
        "sim_work": {
            "prefill_cycles_per_tok": round(REF_PREFILL_CYCLES * pre_scale,
                                            2),
            "decode_cycles_per_tok": round(REF_DECODE_CYCLES * dec_scale, 2),
        },
        "flops_ratio_prefill": round(pre_ratio, 4),
        "flops_ratio_decode": round(dec_ratio, 4),
    }


def _timeline_summary(tl: RegionTimeline, per_tok: Optional[int] = None
                      ) -> Dict:
    out = {
        "n_regions": len(tl.regions),
        "est_us": round(tl.est_us, 3),
        "flops": tl.flops,
        "mxu_flops": tl.mxu_flops,
        "bytes": tl.bytes,
        "heavy_share": round(tl.heavy_share, 4),
        "vpu_share": round(tl.level_share(1), 4),
        "mxu_share": round(tl.level_share(2), 4),
        "warnings": list(tl.warnings),
    }
    if per_tok:
        out["flops_per_tok"] = tl.flops / per_tok
    return out


# --------------------------------------------------------- full pipeline


def _kernel_differentials(tol: float) -> Dict[str, Optional[Dict]]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import chacha20_keystream, flash_attention

    key = jnp.asarray(np.arange(8), jnp.uint32)
    nonce = jnp.zeros((3,), jnp.uint32)
    q = jnp.zeros((1, 4, 256, 64), jnp.float32)
    out = {}
    d = differential(
        lambda k, n: chacha20_keystream(k, n, 1, n_blocks=64, tile=64,
                                        interpret=True),
        key, nonce, name="chacha20", tol=tol)
    out["chacha20"] = d.to_dict() if d else None
    d = differential(lambda a, b, c: flash_attention(a, b, c, interpret=True),
                     q, q, q, name="flash_attention", tol=tol)
    out["flash_attention"] = d.to_dict() if d else None
    return out


def _model_differential(arch: str, tol: float) -> Optional[Dict]:
    """Static vs HLO on the reduced config (the only one CPU compiles in
    reasonable time), prompt 64 — the same shape launch.serve jits."""
    import jax

    from repro.configs import get_arch
    from repro.dist.context import no_dist
    from repro.models.api import build_model

    cfg = get_arch(arch).reduced()
    model = build_model(cfg, no_dist())
    params = model.init(jax.random.key(0))
    specs, _ = model.input_specs(_CalibShape(64, "prefill"))
    batch = jax.tree.map(
        lambda s: jax.numpy.zeros(s.shape, s.dtype), specs)

    def prefill(p, b):
        cache = model.init_cache(p, b, 1, 128)
        return model.prefill(p, b, cache)

    d = differential(prefill, params, batch, name=f"{arch}/prefill",
                     tol=tol)
    return d.to_dict() if d else None


def run_calibration(archs: Optional[List[str]] = None,
                    with_differential: bool = True,
                    tol: float = FLOPS_REL_TOL) -> Dict:
    from repro.configs import arch_ids, get_arch

    machine = MachineModel()
    archs = list(archs or arch_ids())

    kernels: Dict[str, Dict] = {}
    for tl in kernel_timelines(machine):
        kernels[tl.name] = _timeline_summary(tl)
        kernels[tl.name]["tags"] = tag_heavy([tl])
    if with_differential:
        for name, d in _kernel_differentials(tol).items():
            if name in kernels:
                kernels[name]["differential"] = d

    ref_tls = model_timelines(REF_ARCH, machine=machine)
    ref_pre_flops_tok = ref_tls["prefill"].flops / CALIB_PROMPT
    ref_dec_flops = ref_tls["decode_step"].flops

    workloads: Dict[str, Dict] = {}
    for arch in archs:
        family = get_arch(arch).family
        tls = ref_tls if arch == REF_ARCH \
            else model_timelines(arch, machine=machine)
        pre, dec = tls["prefill"], tls["decode_step"]
        entry = {
            "family": family,
            "prefill": _timeline_summary(pre, per_tok=CALIB_PROMPT),
            "decode_step": _timeline_summary(dec),
            "tags": tag_heavy([pre, dec]),
            "freq": {
                "levels_ghz": derive_freq_levels(pre),
                "grant_delay_ms": 0.5,
                "hysteresis_ms": 2.0,
            },
            "scenario": derive_scenario(family, pre, dec,
                                        ref_pre_flops_tok, ref_dec_flops),
        }
        if with_differential and arch in DIFFERENTIAL_ARCHS:
            entry["differential"] = _model_differential(arch, tol)
        workloads[arch] = entry

    return {
        "version": 1,
        "generated_by": "PYTHONPATH=src python -m repro.analysis.calibrate "
                        "--update",
        "calib_prompt": CALIB_PROMPT,
        "flops_rel_tol": tol,
        "assumed_while_trips": CostConfig().assumed_while_trips,
        "machine": {"mxu_flops_per_s": machine.mxu_flops_per_s,
                    "vpu_flops_per_s": machine.vpu_flops_per_s,
                    "hbm_bytes_per_s": machine.hbm_bytes_per_s},
        "reference": {"arch": REF_ARCH,
                      "prefill_flops_per_tok": ref_pre_flops_tok,
                      "decode_flops": ref_dec_flops},
        "kernels": kernels,
        "workloads": workloads,
    }


def _table(data: Dict) -> str:
    lines = [f"{'workload':20s} {'fam':>6s} {'MXU%':>5s} {'f1':>5s} "
             f"{'f2':>5s} {'rate':>5s} {'pre_cyc':>8s} {'tags'}"]
    for arch, w in sorted(data["workloads"].items()):
        f = w["freq"]["levels_ghz"]
        sc = w["scenario"]
        lines.append(
            f"{arch:20s} {w['family']:>6s} "
            f"{100 * w['prefill']['mxu_share']:5.1f} {f[1]:5.2f} "
            f"{f[2]:5.2f} {sc['rate_per_s']:5.2f} "
            f"{sc['sim_work']['prefill_cycles_per_tok']:8.1f} "
            f"{','.join(w['tags'])}")
    lines.append("")
    for name, k in sorted(data["kernels"].items()):
        d = k.get("differential")
        dd = (f"diff rel_err={d['rel_err']:.3f} "
              f"{'OK' if d['agrees'] else 'DIVERGED'}") if d else ""
        lines.append(f"{name:20s} {'':>6s} {100 * k['mxu_share']:5.1f} "
                     f"heavy={k['heavy_share']:.2f} est={k['est_us']:.1f}us "
                     f"{dd}")
    for arch, w in sorted(data["workloads"].items()):
        d = w.get("differential")
        if d:
            lines.append(f"{arch:20s} diff(reduced) "
                         f"rel_err={d['rel_err']:.3f} "
                         f"{'OK' if d['agrees'] else 'DIVERGED'}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", action="store_true",
                    help=f"rewrite {DERIVED_PATH}")
    ap.add_argument("--no-differential", action="store_true",
                    help="skip the (slow) static-vs-HLO compile checks")
    ap.add_argument("--out", default=None,
                    help="also write the full JSON here")
    args = ap.parse_args(argv)

    data = run_calibration(with_differential=not args.no_differential)
    print(_table(data))
    diverged = [
        n for n, k in list(data["kernels"].items())
        + list(data["workloads"].items())
        if k.get("differential") and not k["differential"]["agrees"]
        and n not in KNOWN_DIVERGENT]
    if diverged:
        print(f"\nstatic-vs-HLO DIVERGED beyond tol: {diverged}",
              file=sys.stderr)
    text = json.dumps(data, indent=1, sort_keys=True) + "\n"
    if args.update:
        DERIVED_PATH.write_text(text)
        print(f"\nwrote {DERIVED_PATH}")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    return 1 if diverged else 0


if __name__ == "__main__":
    raise SystemExit(main())
