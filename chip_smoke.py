#!/usr/bin/env python3
"""Chip smoke test: the served MoE path on a TPU at qwen3-30b-a3b's
published widths (d_model 2048, 32/4 heads x 128, 128 experts top-8, expert
d_ff 768, vocab 151936, bf16), depth cut to 4 layers, random weights from a
seed.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: the replica phase only

(a) kernels against XLA: ``M.prefill`` and ``M.decode_step_paged`` with the
    fused MoE dispatch and Pallas kernels (compiled) against the dense
    dispatch and XLA attention, on the same inputs and placement: one layer
    in bf16, and two layers in f32 at full matmul precision.  Every token
    must be routed to the same experts on both sides, and the logits must
    agree within tests/test_kernels.py's tolerance for the dtype.
(b) serving: ``launch.serve.build_cluster`` -> Cluster -> Engine ->
    SchedulerCore -> JaxBackend serves a few seeded requests (two share a
    prefix) under the gimbal variant with a small expert-replacement period;
    every request must finish and the expert level must relocate weights.
--chips 4: the phase-(b) trace on four one-chip replicas behind the router
    against a one-engine run on chip 0 (expert rebalancing off in both):
    each engine on its own chip, each serving, every request finished, and
    every token stream equal to the reference.

The script exits non-zero before any phase when JAX finds no TPU.  Its last
line is ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` where set, else
``.jax_cache/`` in this checkout.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = {"bfloat16": 5e-2, "float32": 2e-4}     # rtol = atol, tests/test_kernels.py
DEPTH = 4                   # layers served in phase (b) and --chips 4
KERNEL_CHECKS = ((1, "bfloat16"), (2, "float32"))   # phase (a): (layers, dtype)
MAX_SLOTS, MAX_SEQ, PAGE = 8, 2048, 16


class CompileClock:
    """Running totals of XLA backend compile seconds (a persistent-cache
    load counts as its retrieval time) and of persistent-cache hits, from
    jax.monitoring.  Tracing is not counted: nested jits would count twice."""

    def __init__(self):
        import jax
        self.total = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _phase(clock, name, fn, *args, **kw):
    c0, h0, t0 = clock.total, clock.hits, time.perf_counter()
    out = fn(*args, **kw)
    print(f"[{name}] compile_s={clock.total - c0:.2f} "
          f"cache_hits={clock.hits - h0} "
          f"wall_s={time.perf_counter() - t0:.2f}", flush=True)
    return out


def full_config():
    from repro.launch.serve import model_config
    return model_config("qwen3-30b-a3b", "full", DEPTH)


# --------------------------------------------------------------------- phase a
def phase_kernels(cfg, *, prompt_lens=(300, 400, 500), decode_steps=4,
                  max_seq=MAX_SEQ, seed=0, interpret=False):
    """Fused dispatch + Pallas kernels vs dense dispatch + XLA attention at
    prefill and through ``decode_steps`` paged decode steps (teacher-forced
    with the XLA side's greedy tokens, so both sides see the same inputs).

    Top-k routing is discontinuous: a rounding difference in a router's
    input can swap a token's k-th and (k+1)-th expert, and its logits then
    differ by a whole expert's share, which no tolerance separates from a
    fault.  So the checks are built to route alike: one bf16 layer (the
    router sees the same input on both sides at prefill), or f32 at full
    matmul precision (TPU's default takes one bf16 pass).  Every row (token)
    must be routed alike in every layer and every row's logits must agree
    within ``TOL[cfg.dtype]``.  Returns {"max_err" (also split into
    "max_err_prefill" and "max_err_decode"), "tol_used" (largest |a-b| /
    (tol + tol*|b|): at most 1 passes), "routed_alike" (share of rows),
    "rows", "ok", "custom_call"}."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import init_params
    from repro.models import model as M
    from repro.serving.backend import _bucket
    from repro.serving.kvcache import PagedKVCache

    tol = TOL[cfg.dtype]
    precision = (jax.default_matmul_precision("highest")
                 if cfg.dtype == "float32" else contextlib.nullcontext())
    params = init_params(cfg, seed)
    rng = np.random.default_rng(seed)
    sides = {"kernels": dict(dispatch_mode="fused", use_kernel=True),
             "xla": dict(dispatch_mode="dense", use_kernel=False)}
    rows = {"err": [], "same": [], "ratio": [], "decode": []}

    def compare(out, n, decode):
        """out[side] = (logits (n, V), expert ids (L, n, K))."""
        a, b = (out[s][0].astype(jnp.float32) for s in sides)
        diff = jnp.abs(a - b)
        rows["err"] += list(np.asarray(diff.max(-1)))
        rows["decode"] += [decode] * n
        rows["ratio"] += list(np.asarray((diff / (tol + tol * jnp.abs(b))).max(-1)))
        ids = [np.sort(np.asarray(out[s][1]).reshape(-1, n, cfg.moe_top_k), -1)
               for s in sides]
        rows["same"] += list((ids[0] == ids[1]).all(axis=(0, 2)))

    bucket = max(_bucket(n) for n in prompt_lens)
    with precision:
        prefill = {
            name: jax.jit(lambda p, t, kw=kw: M.prefill(
                p, cfg, t, M.init_cache(cfg, 1, max_seq), stats=True,
                dispatch_mode=kw["dispatch_mode"], interpret=interpret))
            for name, kw in sides.items()}
        kv = {name: PagedKVCache(cfg, MAX_SLOTS, max_seq, block_size=PAGE)
              for name in sides}
        nxt = np.zeros((MAX_SLOTS,), np.int32)
        for row, n in enumerate(prompt_lens):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = rng.integers(0, cfg.vocab_size, n)
            out = {name: prefill[name](params, jnp.asarray(toks)) for name in sides}
            compare({s: (o[0][0, :n], o[2]["expert_ids"][:, 0, :n])
                     for s, o in out.items()}, n, False)
            for name in sides:
                slot = kv[name].alloc(n)
                assert slot == row
                kv[name].write_prefill(slot, out[name][1])
                kv[name].slot_len[slot] = n
            nxt[row] = int(jnp.argmax(out["xla"][0][0, n - 1]))
            del out

        decode, custom_call = {}, False
        for name, kw in sides.items():
            args = (params, jnp.asarray(nxt[:, None]), kv[name].pages,
                    jnp.asarray(kv[name].block_tables),
                    jnp.asarray(kv[name].positions()))
            decode[name] = jax.jit(lambda p, t, pg, bt, ln, kw=kw: M.decode_step_paged(
                p, cfg, t, pg, bt, ln, stats=True, interpret=interpret, **kw)
            ).lower(*args).compile()
            if name == "kernels":
                custom_call = "tpu_custom_call" in decode[name].as_text()
        n = len(prompt_lens)
        for _ in range(decode_steps):
            out = {}
            for name in sides:
                for slot in range(n):
                    kv[name].prepare_append(slot)
                logits, kv[name].pages, aux = decode[name](
                    params, jnp.asarray(nxt[:, None]), kv[name].pages,
                    jnp.asarray(kv[name].block_tables),
                    jnp.asarray(kv[name].positions()))
                kv[name].slot_len[:n] += 1
                out[name] = (logits[:n], aux["expert_ids"][:, :n])
            compare(out, n, True)
            nxt[:n] = np.asarray(jnp.argmax(out["xla"][0], -1))
    same, err, dec, ratio = (np.asarray(rows[k])
                             for k in ("same", "err", "decode", "ratio"))
    return {"max_err": float(err.max()), "max_err_prefill": float(err[~dec].max()),
            "max_err_decode": float(err[dec].max()),
            "tol_used": float(ratio.max()), "routed_alike": float(same.mean()),
            "rows": len(same), "ok": bool(same.all() and ratio.max() <= 1.0),
            "custom_call": custom_call}


# --------------------------------------------------------------------- phase b
def serve_trace(cfg, *, n=8, seed=0, prompt_range=(32, 1500),
                new_range=(16, 64)):
    """``n`` requests with seeded prompt tokens; request 1 repeats request
    0's first half-prompt (same length, so the same prefill bucket)."""
    import numpy as np
    from repro.core.types import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_range[0], prompt_range[1] + 1, n)
    lens[1] = lens[0]
    news = rng.integers(new_range[0], new_range[1] + 1, n)
    prompts = [rng.integers(0, cfg.vocab_size, int(m)).astype(np.int32)
               for m in lens]
    half = int(lens[0]) // 2
    prompts[1][:half] = prompts[0][:half]
    return [Request(req_id=i, prompt_len=int(lens[i]),
                    max_new_tokens=int(news[i]), arrival_time=0.02 * i,
                    prompt_tokens=prompts[i]) for i in range(n)]


def phase_serve(cfg, trace, *, n_engines=1, devices=None, tau=4,
                max_seq=MAX_SEQ):
    """Serve ``trace`` through build_cluster's Cluster (gimbal variant; the
    expert level re-places every ``tau`` engine steps, ``tau=None`` turns it
    off).  The engines first compile their programs for the trace's prompt
    buckets side by side, one thread each.  Returns a summary with
    per-request token streams."""
    import copy
    import jax
    from repro.core.types import GimbalConfig
    from repro.launch.serve import build_cluster

    gcfg = GimbalConfig(tau=tau or 10 ** 9, theta_load=64)
    devices = list(devices) if devices is not None else jax.devices()[:1]
    cluster = build_cluster(cfg, "gimbal", n_engines, gcfg,
                            max_slots=MAX_SLOTS, max_seq=max_seq,
                            prefill_budget=max_seq, devices=devices)
    engines = list(cluster.engines.values())
    # each engine's programs compile for its own device: overlap them
    with ThreadPoolExecutor(len(engines)) as pool:
        list(pool.map(lambda e: e.backend.warmup(
            [r.prompt_len for r in trace]), engines))
    reqs = [copy.copy(r) for r in trace]
    for r in reqs:
        cluster.submit(r, r.arrival_time)
    cluster.run_until_drained(t0=reqs[-1].arrival_time, dt=0.05,
                              max_steps=20_000)
    on_own = all(
        set().union(*(leaf.devices() for leaf in
                      jax.tree.leaves((e.params, e.kv.pages)))) == {devices[i]}
        for i, e in enumerate(engines))
    done = {r.req_id: r for r in cluster.finished}
    return {
        "sent": len(reqs),
        "finished": len(done),
        "tokens": sum(r.generated for r in done.values()),
        "streams_whole": all(len(r.output_tokens) == r.generated
                             for r in done.values()),
        "relocations": sum(e.relocations for e in engines),
        "shared_hits": sum(e.kv.shared_hits for e in engines),
        "served_per_engine": [sum(r.engine_id == e.engine_id
                                  for r in done.values()) for e in engines],
        "on_own_device": on_own,
        "kernel_modes": sorted({e.backend.kernel_mode for e in engines}),
        "streams": {i: list(r.output_tokens) for i, r in done.items()},
    }


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------- replicas
def phase_replicas(cfg, trace, devices, *, max_seq=MAX_SEQ):
    """The trace on len(devices) one-chip replicas vs one engine on
    devices[0], expert rebalancing off in both."""
    ref = phase_serve(cfg, trace, n_engines=1, devices=devices[:1], tau=None,
                      max_seq=max_seq)
    gc.collect()                    # free the reference engine's chip memory
    many = phase_serve(cfg, trace, n_engines=len(devices), devices=devices,
                       tau=None, max_seq=max_seq)
    many["reference_finished"] = ref["finished"]
    many["streams_equal"] = many["streams"] == ref["streams"]
    return many


def _check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    _check(len(devices) >= args.chips,
           f"--chips {args.chips} but JAX sees {len(devices)} devices")

    from repro.launch.serve import use_compile_cache
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devices)}")
    print(f"compile_cache={use_compile_cache()}")
    clock = CompileClock()
    cfg = full_config()
    print(f"model={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"experts={cfg.num_experts} top_k={cfg.moe_top_k} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}", flush=True)
    trace = serve_trace(cfg)

    if args.chips == 4:
        rep = _phase(clock, "replicas", phase_replicas, cfg, trace, devices[:4])
        print(f"[replicas] engines=4 on_own_device={rep['on_own_device']} "
              f"served_per_engine={rep['served_per_engine']} "
              f"finished={rep['finished']}/{rep['sent']} "
              f"reference_finished={rep['reference_finished']}/{rep['sent']} "
              f"tokens={rep['tokens']} streams_equal={rep['streams_equal']}")
        print("[replicas] peak_bytes_in_use="
              + ",".join(str(peak_bytes(d)) for d in devices[:4]))
        _check(rep["on_own_device"], "an engine's arrays are off its chip")
        _check(min(rep["served_per_engine"]) >= 1, "an engine served nothing")
        _check(rep["finished"] == rep["sent"] == rep["reference_finished"],
               "unfinished requests")
        _check(rep["streams_equal"], "token streams differ from 1 engine")
    else:
        from repro.configs import at_depth
        for depth, dtype in KERNEL_CHECKS:
            kcfg = at_depth(cfg, depth).replace(dtype=dtype)
            name = f"kernels_vs_xla {dtype}"
            a = _phase(clock, name, phase_kernels, kcfg)
            print(f"[{name}] layers={depth} "
                  f"rows={a['rows']} routed_alike={a['routed_alike']:.4f} "
                  f"max_abs_logit_err={a['max_err']:.6g} "
                  f"(prefill {a['max_err_prefill']:.6g}, "
                  f"decode {a['max_err_decode']:.6g}) "
                  f"tol_used={a['tol_used']:.6g} (rtol=atol={TOL[dtype]}) "
                  f"ok={a['ok']} decode_hlo_tpu_custom_call={a['custom_call']}",
                  flush=True)
            _check(a["ok"], f"fused+kernels differ from dense+XLA ({dtype})")
            _check(a["custom_call"], "no Mosaic kernel in the decode step")
            gc.collect()
        b = _phase(clock, "serve", phase_serve, cfg, trace)
        print(f"[serve] kernel_mode={','.join(b['kernel_modes'])} "
              f"finished={b['finished']}/{b['sent']} tokens={b['tokens']} "
              f"relocations={b['relocations']} "
              f"paged_kv_shared_hits={b['shared_hits']}")
        peak = peak_bytes(dev)
        print(f"[serve] peak_bytes_in_use={peak}")
        _check(b["kernel_modes"] == ["compiled"], "kernels not compiled")
        _check(b["finished"] == b["sent"] and b["streams_whole"],
               "unfinished requests")
        _check(b["relocations"] >= 1, "the expert level never relocated")
        _check(b["shared_hits"] >= 1, "no paged-KV prefix sharing")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
