"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

A cluster of real JAX engines serving a BurstGPT- or ShareGPT-shaped trace
with the full Gimbal stack, health monitoring, and optional fault injection —
the deployment-shaped entry point.

Every engine serves through the paged KV pool (16-token pages) with the
fused MoE dispatch and the Pallas kernels on the decode path; the kernels are
compiled on a TPU and interpreted elsewhere (``JaxBackend.kernel_mode``).
``--size smoke`` (the default) serves the arch's reduced CPU config;
``--size full --depth N`` serves its published widths cut to N layers (the
depth is required: each engine holds all its weights on one device).
Replica *i* serves from ``jax.devices()[i]`` (round-robin when there are
fewer devices than engines), and every replica holds the same weights.

The run exits non-zero when any request is still unfinished at ``HORIZON``.

The summary's TTFT, TPOT and rate are on the logical clock (each step
advances it a fixed ``dt``), not measured.  Beside them it prints what the
in-program recorder (``repro.core.trace``) measured on the host's clock:
the mean queue wait, prefill and decode step, and the compiles among them
(this entry point compiles as it serves).
"""
from __future__ import annotations

import argparse
import copy
import os
import sys
from pathlib import Path
from typing import Optional, Sequence

import jax

from repro.configs import at_depth, get_config, get_smoke_config, list_archs
from repro.core import trace as tracer
from repro.core.types import GimbalConfig
from repro.distributed.fault import HealthConfig, HealthMonitor
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.cluster import Cluster
from repro.serving.engine import Engine
from repro.workloads.burstgpt import burstgpt_trace
from repro.workloads.sharegpt import sharegpt_trace

REPO_ROOT = Path(__file__).resolve().parents[3]
HORIZON = 120.0        # logical seconds served before unfinished requests fail
# the summary's measured means: label -> recorder span
MEASURED = {"queue wait": "request.queued", "prefill": "backend.prefill",
            "decode step": "backend.decode", "compile": "compile"}


def measured_means(records) -> dict:
    """``{span: (count, mean ms)}`` of the recorder's records for each span
    the summary prints."""
    by = {name: [] for name in MEASURED.values()}
    for r in records:
        got = by.get(r.name[len(tracer.PREFIX):])
        if got is not None:
            got.append(r.ms)
    return {k: (len(v), sum(v) / len(v) if v else 0.0)
            for k, v in by.items()}


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself), else the
    fixed ``.jax_cache/`` of this checkout."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def model_config(arch: str, size: str = "smoke",
                 depth: Optional[int] = None) -> ModelConfig:
    """The served config: the reduced smoke variant, or the published widths
    (``size="full"``), optionally cut to ``depth`` layers."""
    if size == "smoke":
        return get_smoke_config(arch)
    cfg = get_config(arch)
    return at_depth(cfg, depth) if depth else cfg


def init_params(cfg: ModelConfig, seed: int = 0,
                device: Optional[jax.Device] = None):
    """Random weights from ``seed``, generated by one compiled program
    straight onto ``device`` (no per-layer host-side copies)."""
    device = device if device is not None else jax.devices()[0]
    return jax.jit(M.init_params, static_argnums=1,
                   out_shardings=jax.sharding.SingleDeviceSharding(device))(
        jax.random.key(seed), cfg)


def build_cluster(cfg: ModelConfig, variant: str, n_engines: int,
                  gcfg: GimbalConfig, *, max_slots: int = 4,
                  max_seq: int = 128, prefill_budget: int = 128,
                  devices: Optional[Sequence] = None) -> Cluster:
    """``n_engines`` replicas of one model behind the router.  Engine *i*
    serves from ``devices[i % len(devices)]`` (default ``jax.devices()``)
    with the same weights (one key).  The expert level places the routed
    experts the model holds (``cfg.num_experts``: a share of the router's
    ``router_width`` where the chip holds one) over up to 4 devices."""
    devices = list(devices) if devices is not None else jax.devices()
    params = init_params(cfg, device=devices[0])
    engines = []
    for i in range(n_engines):
        engines.append(Engine(
            i, cfg, params, variant=variant, gimbal_cfg=gcfg,
            max_slots=max_slots, max_seq=max_seq,
            prefill_budget=prefill_budget,
            num_expert_devices=max(2, min(4, cfg.num_experts or 2)),
            kv_layout="paged", kv_block_size=16, dispatch_mode="fused",
            use_kernels=True, device=devices[i % len(devices)]))
    return Cluster(engines, variant=variant, gimbal_cfg=gcfg)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-30b-a3b", choices=list_archs())
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--depth", type=int, default=None,
                    help="layers kept of the full config (needed by --size full)")
    ap.add_argument("--variant", default="gimbal",
                    choices=["vllm", "dplb", "sjfs", "edr", "gimbal"])
    ap.add_argument("--engines", type=int, default=2)
    ap.add_argument("--trace", default="burstgpt", choices=["burstgpt", "sharegpt"])
    ap.add_argument("--n", type=int, default=40)
    ap.add_argument("--rps", type=float, default=20.0)
    ap.add_argument("--fail-engine", type=int, default=-1,
                    help="inject a failure of this engine mid-run")
    args = ap.parse_args(argv)
    if args.size == "full" and not args.depth:
        ap.error("--size full needs --depth")

    use_compile_cache()
    gcfg = GimbalConfig(tau=25, theta_load=64)
    cfg = model_config(args.arch, args.size, args.depth)
    cluster = build_cluster(cfg, args.variant, args.engines, gcfg)
    monitor = HealthMonitor(list(cluster.engines), HealthConfig())

    if args.trace == "burstgpt":
        trace = burstgpt_trace(n=args.n, rps=args.rps, seed=0)
        for r in trace:
            r.prompt_len = max(8, r.prompt_len // 50)
            r.max_new_tokens = max(2, r.max_new_tokens // 40)
    else:
        trace = sharegpt_trace(n_requests=args.n, n_users=max(args.n // 8, 1),
                               rps=args.rps, vocab_size=64, utterance_mean=12,
                               answer_mean=8, max_context=96)
        for r in trace:
            r.max_new_tokens = 2

    trace = [copy.copy(r) for r in trace]
    i, now, dt = 0, 0.0, 0.05
    failed_at = None
    tracer.enable()
    try:
        while now <= HORIZON:
            while i < len(trace) and trace[i].arrival_time <= now:
                cluster.submit(trace[i], now)
                i += 1
            cluster.step(now)
            monitor.observe(cluster.bus.snapshot(now), now)
            for eid in monitor.check(now):
                print(f"[serve] t={now:.2f} engine {eid} DEAD -> re-routing")
                cluster.fail_engine(eid, now)
            if (args.fail_engine >= 0 and failed_at is None
                    and i >= len(trace) // 2):
                eid = args.fail_engine
                print(f"[serve] t={now:.2f} injecting failure of engine {eid}")
                moved = cluster.fail_engine(eid, now)
                print(f"[serve] re-routed {moved} requests")
                failed_at = now
            now += dt
            if i >= len(trace) and cluster.drained():
                break
    finally:
        tracer.disable()
    spans = measured_means(tracer.drain())
    rep = cluster.report()
    pf = cluster.prefix_stats()
    relocs = sum(e.relocations for e in cluster.engines.values())
    print(f"[serve] {args.variant} on {cfg.name} ({args.size}, "
          f"{cfg.num_layers} layers): {rep.n}/{len(trace)} done | logical "
          f"clock, {dt:g} s a step: TTFT mean {rep.mean_ttft:.3f}s p99 "
          f"{rep.p99_ttft:.3f}s | TPOT {rep.mean_tpot*1e3:.1f}ms | "
          f"{rep.throughput_tok_s:.0f} tok/s")
    print("[serve] measured (host clock): " + " | ".join(
        f"{label} mean {spans[name][1]:.3f}ms n={spans[name][0]}"
        for label, name in MEASURED.items()))
    print(f"[serve] prefix hits {pf['hit_blocks']} "
          f"(rate {100*pf['hit_rate']:.1f}%) | expert relocations {relocs}")
    unfinished = len(trace) - i + cluster.pending()
    if unfinished:
        print(f"[serve] ERROR: {unfinished} requests unfinished at the "
              f"{HORIZON:g} s horizon", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
