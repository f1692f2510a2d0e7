"""End-to-end serving driver: a 2-engine Gimbal cluster runs a BurstGPT-shaped
trace with REAL jax model execution (reduced Qwen3-family MoE), comparing the
vLLM baseline (RR + FCFS + static experts) against full Gimbal.

Run:  PYTHONPATH=src python examples/serve_burstgpt.py [--n 40] [--variant both]
"""
import argparse
import copy

import jax

from repro.configs import get_smoke_config
from repro.core.types import GimbalConfig
from repro.models import model as M
from repro.serving.cluster import Cluster
from repro.serving.engine import Engine
from repro.workloads.burstgpt import burstgpt_trace


def build_cluster(variant: str, n_engines: int = 2) -> Cluster:
    cfg = get_smoke_config("qwen3-30b-a3b").replace(num_experts=16)
    gcfg = GimbalConfig(tau=20, theta_load=64)
    # ONE cluster-wide expert level (§V-A.1): every engine observes routed
    # stats into the same tracker and applies the same placements
    from repro.core.gimbal import make_cluster_expert_level
    level = make_cluster_expert_level(variant, cfg, n_engines, gcfg)
    # replicas behind the router serve ONE model: one weight key for all
    params = M.init_params(jax.random.key(0), cfg)
    engines = []
    for i in range(n_engines):
        engines.append(Engine(i, cfg, params, variant=variant, gimbal_cfg=gcfg,
                              max_slots=4, max_seq=128, prefill_budget=128,
                              expert_level=level))
    return Cluster(engines, variant=variant, gimbal_cfg=gcfg,
                   expert_level=level)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=40)
    ap.add_argument("--variant", default="both",
                    choices=["vllm", "gimbal", "both"])
    args = ap.parse_args()

    trace = burstgpt_trace(n=args.n, distribution="two-end", rps=20.0, seed=0)
    for r in trace:                       # scale into reduced-model territory
        r.prompt_len = max(8, r.prompt_len // 50)
        r.max_new_tokens = max(2, r.max_new_tokens // 40)

    variants = ["vllm", "gimbal"] if args.variant == "both" else [args.variant]
    for variant in variants:
        c = build_cluster(variant)
        for r in (copy.copy(x) for x in trace):
            c.submit(r, now=r.arrival_time)
        c.run_until_drained(t0=trace[-1].arrival_time + 0.01, dt=0.05)
        rep = c.report()
        relocs = sum(e.relocations for e in c.engines.values())
        xrep = c.expert_report()
        print(f"{variant:7s}: {rep.n} done | mean TTFT {rep.mean_ttft:.3f}s "
              f"p99 {rep.p99_ttft:.3f}s | TPOT {rep.mean_tpot*1e3:.1f}ms | "
              f"{rep.throughput_tok_s:.0f} tok/s | expert relocations {relocs}"
              f" | moe_mult {xrep['moe_mult']:.3f}")


if __name__ == "__main__":
    main()
