"""TPU adaptation benchmark: device-pool specialization for serving
(prefill and decode on separate device pools) — the paper's Fig. 5
analogue on an LLM workload.

Baseline: ``SharedBaselinePolicy`` over one shared pool, chunked prefill
interleaved with decode (every prefill stalls all co-located decodes —
the 2 ms-tail analogue). Specialized: ``SpecializedPolicy`` over a
prefill/decode ``Topology`` with asymmetric stealing and KV handoffs.
Metric: inter-token latency (ITL) tail and its variability. Service
times come from the committed ``POOL_MODEL`` constants, a roofline
estimate for codeqwen1.5-7b (not a device measurement).

  PYTHONPATH=src python benchmarks/serving_specialization.py [--smoke]
"""
from __future__ import annotations

import argparse
import copy
import time

from repro.sched import SharedBaselinePolicy, SpecializedPolicy, Topology
from repro.sched.cluster import (ClusterConfig, ClusterEngine,
                                 ClusterTopology)
from repro.sched.engine import Engine, PoolModel, ServeConfig
from repro.sched.policy import make_cluster_policy
from repro.sched.replay import headline_metrics
from repro.sched.workload import poisson_workload, scenario_trace

POOL_MODEL = PoolModel(prefill_ms_per_ktok=326.0, decode_fixed_ms=757.0,
                       decode_ms_per_seq=23.6)


def run(arch: str = "codeqwen1.5-7b", n_devices: int = 16,
        prefill_devices: int = 4, duration_ms: float = 60_000.0,
        util: float = 0.5, seed: int = 3, scenario: str = None,
        cluster_shards: int = 2,
        cluster_policy: str = "cluster-adaptive"):
    pm = POOL_MODEL
    if scenario is not None:
        # one scenario trace from the workload subsystem, replayed
        # identically under both setups
        wl = scenario_trace(scenario, duration_ms=duration_ms,
                            seed=seed).to_engine_requests()
        rate = len(wl) * 1000.0 / duration_ms
    else:
        # default: auto-calibrate arrival rate to `util` of decode capacity
        dec_dev = n_devices - prefill_devices
        itl_ms = pm.decode_ms(64, dec_dev)
        tok_per_s = 64 * 1000.0 / itl_ms
        max_new = 64
        rate = util * tok_per_s / max_new
        wl = poisson_workload(rate, duration_ms, prompt_len=2048,
                              max_new=max_new, seed=seed)
    cfg = ServeConfig(prefill_chunk=2048, decode_batch_max=256)
    setups = {
        "nospec": (Topology.shared(n_devices), SharedBaselinePolicy()),
        "spec": (Topology.serving(n_devices, prefill_devices),
                 SpecializedPolicy()),
    }
    out = {}
    for key, (topo, policy) in setups.items():
        eng = Engine(topo, policy, pm, cfg)
        m = eng.run(copy.deepcopy(wl), duration_ms)
        out[key] = m.summary()
    ns, sp = out["nospec"], out["spec"]
    if ns["itl_p99_ms"] > 0:
        # the paper's metric: performance VARIABILITY (tail spread) —
        # one shared definition with the scenario-matrix harness
        out.update(headline_metrics(ns, sp))
    if cluster_shards > 0:
        # cluster leg: the same trace behind the frequency-aware router,
        # N full-size nodes vs the single shared node above
        cpol = make_cluster_policy(cluster_policy)
        ct = ClusterTopology.homogeneous(cluster_shards, n_devices,
                                         prefill_devices,
                                         policy=cpol.shard_policy)
        ceng = ClusterEngine(ct, cluster_policy, pm,
                             ClusterConfig(serve=cfg))
        cm = ceng.run(copy.deepcopy(wl), duration_ms)
        out["cluster"] = cm.summary()
        out["cluster_shards"] = cluster_shards
        out["cluster_policy"] = cluster_policy
        out["cluster_shard_summaries"] = cm.shard_summaries()
        if ns["itl_p99_ms"] > 0:
            out["cluster_vs_shared"] = headline_metrics(ns, out["cluster"])
    out["arch"] = arch
    out["rate_req_s"] = rate
    return out


def rows(duration_ms: float = 60_000.0, scenario: str = None):
    t0 = time.time()
    res = run(duration_ms=duration_ms, scenario=scenario)
    wall = (time.time() - t0) * 1e6 / 2
    out = []
    for k in ("nospec", "spec", "cluster"):
        if k not in res:
            continue
        s = res[k]
        label = k if k != "cluster" \
            else f"cluster{res['cluster_shards']}x"
        out.append((f"serving[{res['arch']}|{label}]", wall,
                    f"itl_p50={s['itl_p50_ms']:.1f}ms "
                    f"itl_p99={s['itl_p99_ms']:.1f}ms "
                    f"ttft_p99={s['ttft_p99_ms']:.0f}ms "
                    f"tok/s={s['throughput_tok_s']:.0f} "
                    f"f={s['avg_freq_ghz']:.2f}GHz "
                    f"lic_res={100 * s['license_residency']:.0f}% "
                    f"thr={s['throttled_ms']:.0f}ms "
                    f"E={s['energy_proxy']:.0f}"))
    out.append(("serving[itl_p99_reduction]", wall,
                f"{100 * res.get('itl_p99_reduction', 0):.0f}%"))
    out.append(("serving[itl_variability_reduction]", wall,
                f"{100 * res.get('itl_variability_reduction', 0):.0f}%"))
    cvs = res.get("cluster_vs_shared")
    if cvs:
        out.append(("serving[cluster_itl_p99_reduction]", wall,
                    f"{100 * cvs['itl_p99_reduction']:.0f}%"))
        out.append(("serving[cluster_variability_reduction]", wall,
                    f"{100 * cvs['itl_variability_reduction']:.0f}%"))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short run (CI regression gate): asserts the "
                         "specialized engine still cuts the ITL tail "
                         "spread vs the shared baseline")
    ap.add_argument("--scenario", default=None,
                    help="replay a registered workload scenario "
                         "(repro.sched.workload.SCENARIOS) instead of "
                         "the calibrated Poisson default")
    args = ap.parse_args(argv)
    if args.smoke:
        res = run(duration_ms=20_000.0, scenario=args.scenario)
        spread_ns = res["itl_spread_shared_ms"]
        spread_sp = res["itl_spread_specialized_ms"]
        print(f"smoke: spread nospec={spread_ns:.1f}ms "
              f"spec={spread_sp:.1f}ms "
              f"variability_reduction="
              f"{100 * res['itl_variability_reduction']:.0f}%")
        assert res["nospec"]["completed"] > 0
        assert res["spec"]["completed"] > 0
        assert spread_sp < spread_ns, (spread_sp, spread_ns)
        cvs = res.get("cluster_vs_shared")
        if cvs:
            print(f"smoke: cluster({res['cluster_shards']}x "
                  f"{res['cluster_policy']}) "
                  f"itl_p99_reduction={100 * cvs['itl_p99_reduction']:.0f}% "
                  f"variability_reduction="
                  f"{100 * cvs['itl_variability_reduction']:.0f}%")
            assert res["cluster"]["completed"] > 0
            assert cvs["itl_p99_reduction"] > 0, cvs
        print("smoke: OK")
        return
    for r in rows(scenario=args.scenario):
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()
