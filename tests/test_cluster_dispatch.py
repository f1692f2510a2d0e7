"""Cluster-level dispatch regressions on the cost-model plane (fast lane).

A Cluster can drive SimEngines directly (SimEngine exposes the serving
Engine's step/queue/healthy surface), so the REAL dispatch, hedging, and
fault paths run without JAX compiles:

  * fail_engine() purges the dead engine's PrefixDirectory entries — orphans
    are never routed back to a dead engine's stale prefix, and re-routing
    re-advertises their blocks on the new engine;
  * a hedged move lands in the directory and the assignment log before the
    next submit consults them;
  * run_until_drained counts unhealthy engines' queues (the ISSUE-6 bug:
    requests stranded on a failed-then-restored engine were silently dropped
    from the finished set), with a restore-mid-drain drill via on_step;
  * end-to-end, "combined" dispatch beats "rr" on prefix hit rate on a
    sticky session workload (the campaign cell's fast twin).
"""
import numpy as np
import pytest

from repro.core.types import GimbalConfig, Request
from repro.core.gimbal import make_sim_expert_level
from repro.models.config import ModelConfig
from repro.serving.cluster import Cluster
from repro.sim.costmodel import CostModel, PROFILES
from repro.sim.simulator import SimEngine


def tiny_moe():
    return ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                       num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                       vocab_size=64, num_experts=4, moe_top_k=2, moe_d_ff=32,
                       capacity_factor=8.0, dtype="float32")


def make_cluster(n=2, variant="combined", gcfg=None, max_running=8,
                 prefill_budget=256, kv_pool_tokens=4096):
    gcfg = gcfg or GimbalConfig(tau=10_000)
    cfg = tiny_moe()
    level = make_sim_expert_level(variant, cfg, n, gcfg)
    engines = [SimEngine(i, CostModel(cfg, PROFILES["a100"], n), gcfg,
                         sjf=True, expert_level=level,
                         prefill_budget=prefill_budget,
                         max_running=max_running,
                         kv_pool_tokens=kv_pool_tokens)
               for i in range(n)]
    return Cluster(engines, variant=variant, gimbal_cfg=gcfg)


def req(rid, n_blocks=2, base=0, user=None, t=0.0, out=4):
    tokens = np.arange(base, base + n_blocks * 16, dtype=np.int64)
    return Request(req_id=rid, prompt_len=len(tokens), max_new_tokens=out,
                   arrival_time=t, user_id=user, prompt_tokens=tokens)


# --- directory invalidation on engine failure -------------------------------

def test_fail_engine_purges_directory_and_reroutes():
    c = make_cluster(n=2, variant="combined")
    for i in range(4):
        c.submit(req(i, user="u"), 0.0)
    # empty metrics + sticky: everything lands on engine 0 and advertises there
    assert all(eid == 0 for _, eid in c.dispatch.assignment_log())
    tokens = req(99).prompt_tokens
    assert c.dispatch.directory.blocks_held(0) > 0
    assert c.dispatch.directory.best_engine(tokens)[0] == 0

    n_rerouted = c.fail_engine(0, 0.1)
    assert n_rerouted == 4
    # the dead engine's advertised prefixes are gone (stale entries must not
    # attract the orphans), its cache is empty, and the orphans' re-routing
    # re-advertised their blocks on the surviving engine
    assert c.dispatch.directory.blocks_held(0) == 0
    assert len(c.engines[0].prefix) == 0
    assert c.dispatch.directory.best_engine(tokens)[0] == 1
    assert 0 not in c.router.engine_ids
    # the next same-prefix submit follows the directory to the new engine
    assert c.submit(req(50, user="u"), 0.2) == 1
    done = c.run_until_drained(t0=0.3, dt=0.05)
    assert len(done) == 5                      # nothing lost in the failover


def test_restore_engine_rejoins_dispatch():
    c = make_cluster(n=2, variant="combined")
    c.fail_engine(0, 0.0)
    c.restore_engine(0)
    assert 0 in c.router.engine_ids
    assert c.engines[0].healthy


# --- hedged move updates directory + assignment log --------------------------

def test_hedged_move_updates_directory_before_next_submit():
    gcfg = GimbalConfig(tau=10_000, hedge_threshold=0.5, metric_staleness=5.0)
    c = make_cluster(n=2, variant="combined", gcfg=gcfg, max_running=1)
    # engine 0: one long-running request holding the single slot...
    r0 = req(0, n_blocks=1, base=10_000, out=500)
    r0.engine_id = 0
    c.engines[0].submit(r0, 0.0)
    c.engines[0].step(0.0)
    assert c.engines[0].num_active() == 1
    # ...and one stuck in its queue (this is the hedge candidate)
    r1 = req(1, n_blocks=2, base=20_000, out=4)
    r1.engine_id = 0
    c.engines[1].submit(req(9, n_blocks=1, base=70_000), 0.0)  # 1 not idle
    c.engines[0].submit(r1, 0.0)
    for e in c.engines.values():
        c.bus.publish(e.metrics(0.0))

    c.step(1.0)                    # waited 1.0 >= threshold: hedges 0 -> 1
    assert r1.engine_id == 1 and r1.hedges == 1
    # the move is in the assignment log AND the directory advertises r1's
    # blocks on the target — both before any further submit
    assert (1, 1) in c.dispatch.assignment_log()
    held = c.dispatch.directory.longest_prefix(r1.prompt_tokens)
    assert held.get(1, 0) == len(r1.prompt_tokens)
    # so the user's follow-up with the same prefix lands on the target
    assert c.submit(req(2, n_blocks=2, base=20_000), 1.1) == 1


# --- run_until_drained vs unhealthy queues (the ISSUE-6 bug) -----------------

def test_run_until_drained_waits_for_restored_engine():
    """An engine that goes unhealthy WITHOUT being drained (crash-restart,
    not fail-over) strands its requests; the drain loop must keep going —
    not declare victory over the healthy engines only — so a mid-drain
    restore lets the stranded requests finish."""
    c = make_cluster(n=2, variant="rr")
    for i in range(6):
        c.submit(req(i, base=1000 * i), 0.0)
    per_engine = [c.engines[e].num_active() + len(c.engines[e].queue)
                  for e in (0, 1)]
    assert min(per_engine) > 0                 # rr spread work on both
    c.engines[0].healthy = False               # crash: nothing drained

    restored_at = []

    def restore(cluster, now):
        if now >= 0.3 and not restored_at:
            cluster.restore_engine(0)
            restored_at.append(now)

    done = c.run_until_drained(t0=0.0, dt=0.05, max_steps=2000,
                               on_step=restore)
    assert restored_at, "drill never fired"
    assert len(done) == 6                      # nobody silently dropped


def test_run_until_drained_healthy_cluster_unaffected():
    c = make_cluster(n=2, variant="combined")
    for i in range(4):
        c.submit(req(i, base=500 * i), 0.0)
    done = c.run_until_drained(t0=0.0, dt=0.05, max_steps=2000)
    assert len(done) == 4


def test_run_until_drained_raises_when_requests_remain():
    """Stranded work is an error, not a short finished list: an engine that
    stays down keeps its requests, and the drain gives up loudly."""
    c = make_cluster(n=2, variant="rr")
    for i in range(4):
        c.submit(req(i, base=1000 * i), 0.0)
    c.engines[0].healthy = False               # never restored
    with pytest.raises(RuntimeError, match="not drained after 200 steps"):
        c.run_until_drained(t0=0.0, dt=0.05, max_steps=200)
    assert c.pending() > 0 and not c.drained()
    assert len(c.finished) < 4


# --- end-to-end: combined beats rr on a sticky session workload --------------

def test_combined_beats_rr_on_session_prefix_hits():
    """The campaign acceptance cell's fast twin: per-user growing transcripts
    (workloads.tenants sessions mode) give combined dispatch real prefix
    locality to exploit; round-robin splits each user across engines."""
    import copy
    from repro.workloads import suite_trace
    trace = suite_trace("chat_vs_batch", n=80, arrival="poisson", rps=20.0,
                        seed=3, sessions=True, vocab_size=5000,
                        max_context=256)
    rates = {}
    for variant in ("rr", "combined"):
        c = make_cluster(n=2, variant=variant, max_running=16,
                         prefill_budget=1024, kv_pool_tokens=32_768)
        for r in sorted(trace, key=lambda r: r.arrival_time):
            c.submit(copy.copy(r), r.arrival_time)
        done = c.run_until_drained(t0=trace[-1].arrival_time, dt=0.05,
                                   max_steps=5000)
        assert len(done) == len(trace)
        rates[variant] = c.prefix_stats()["hit_rate"]
    assert rates["combined"] > rates["rr"] > 0.0
