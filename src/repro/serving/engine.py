"""A single DP inference engine: a thin shell over the unified SchedulerCore
(core/scheduler.py) with the real-compute JaxBackend (serving/backend.py).

Every scheduling decision — SJF/FCFS waiting queue with aging, chunked-prefill
admission budget, continuous-batching slot allocation, priority preemption and
victim selection, KV accounting, per-step metrics — lives in SchedulerCore and
is byte-identical to the discrete-event simulator's (sim/simulator.py); see
tests/test_scheduler_parity.py.  This class only wires the backend, the
variant-selected queue, and the expert level together and preserves the
historical public surface (slots, KV cache, counters) for callers and tests.

Timing is *logical*: callers pass ``now`` (the cluster/simulator owns the
clock), so behaviour tests are deterministic.
"""
from __future__ import annotations

from typing import Any, List, Optional

from repro.core.eplb import ExpertRebalancer, NullExpertLevel
from repro.core.gimbal import make_queue, make_rebalancer
from repro.core.scheduler import SchedulerCore
from repro.core.types import EngineMetrics, GimbalConfig, Request
from repro.models import config as mcfg
from repro.serving.backend import JaxBackend

class _Private:
    """Sentinel: build this engine its own expert level.  (A class with a
    stable repr, not a bare object(), so generated API docs stay
    deterministic.)"""

    def __repr__(self):
        return "<build a private expert level>"


_PRIVATE = _Private()


class Engine:
    def __init__(self, engine_id: int, model_cfg: mcfg.ModelConfig, params: Any, *,
                 variant: str = "gimbal", gimbal_cfg: Optional[GimbalConfig] = None,
                 max_slots: int = 4, max_seq: int = 256, prefill_budget: int = 512,
                 num_expert_devices: int = 4, eos_id: Optional[int] = None,
                 dispatch_mode: str = "dense", expert_level: Any = _PRIVATE,
                 kv_layout: str = "slot", kv_block_size: int = 16,
                 kv_quant: Optional[str] = None, use_kernels: bool = False,
                 role: str = "unified", prefill_mode: str = "chunked",
                 device: Any = None):
        """``expert_level`` should be the ONE ClusterExpertLevel shared by
        every engine of a cluster (core/gimbal.make_cluster_expert_level):
        experts are EP-sharded across all engines' devices (§V-A.1), so
        routed stats from every engine aggregate into the same tracker and
        all engines apply the same placements.  When omitted, the engine
        builds a private level over ``num_expert_devices`` devices (the
        historical single-engine behaviour).

        ``device``: the jax.Device this engine serves on (default: the first
        device); its parameters and KV pool are placed there."""
        self.engine_id = engine_id
        self.cfg = model_cfg
        self.gcfg = gimbal_cfg or GimbalConfig()
        # disaggregated serving role: Cluster.poll_handoffs collects finished
        # prefills off "prefill" engines; DispatchCore routes by role
        self.role = role
        if expert_level is _PRIVATE:
            rebalancer = make_rebalancer(variant, model_cfg,
                                         num_expert_devices, self.gcfg)
        else:
            rebalancer = (None if isinstance(expert_level, NullExpertLevel)
                          else expert_level)
        self.backend = JaxBackend(model_cfg, params, max_slots=max_slots,
                                  max_seq=max_seq, eos_id=eos_id,
                                  dispatch_mode=dispatch_mode,
                                  rebalancer=rebalancer,
                                  kv_layout=kv_layout,
                                  kv_block_size=kv_block_size,
                                  kv_quant=kv_quant, use_kernels=use_kernels,
                                  device=device)
        self.core = SchedulerCore(self.backend, make_queue(variant, self.gcfg),
                                  self.gcfg, prefill_budget=prefill_budget,
                                  engine_id=engine_id, expert_level=rebalancer,
                                  prefill_mode=prefill_mode)

    # ------------------------------------------------------------------ public API
    def submit(self, r: Request, now: float = 0.0) -> bool:
        """False when SLO-aware admission control shed the request."""
        return self.core.submit(r, now)

    def metrics(self, now: float) -> EngineMetrics:
        return self.core.metrics(now)

    def num_active(self) -> int:
        return self.core.num_running()

    def step(self, now: float) -> List[Request]:
        """One continuous-batching iteration.  Returns requests finished this
        step (all decisions in SchedulerCore.step)."""
        _, finished = self.core.step(now)
        return finished

    def drain_all(self, migrate: bool = False) -> List[Request]:
        """Pull every request (waiting + running) off this engine.  Default:
        running ones reset for re-execution elsewhere (KV lost on failure);
        ``migrate=True`` marks their KV as travelling with the re-route, so
        generation progress survives (graceful removal / orchestrated
        failover)."""
        return self.core.drain(migrate=migrate)

    # ------------------------------------------------------------------ delegation
    # Historical surface: scheduling state lives in the core, physical state
    # in the backend; these views keep callers/tests/benchmarks working.
    @property
    def queue(self):
        return self.core.queue

    @property
    def prefix(self):
        return self.core.prefix

    @property
    def rebalancer(self) -> Optional[ExpertRebalancer]:
        return self.core.expert

    @property
    def kv(self):
        return self.backend.kv

    @property
    def params(self):
        return self.backend.params

    @property
    def slot_req(self):
        return self.backend.slot_req

    @property
    def slot_last_token(self):
        return self.backend.slot_last_token

    @property
    def max_slots(self) -> int:
        return self.backend.max_slots

    @property
    def max_seq(self) -> int:
        return self.backend.max_seq

    @property
    def steps(self) -> int:
        return self.core.steps

    @property
    def preemptions(self) -> int:
        return self.core.preemptions

    @property
    def relocations(self) -> int:
        return self.backend.relocations

    @property
    def prefill_budget(self) -> int:
        return self.core.prefill_budget

    @prefill_budget.setter
    def prefill_budget(self, v: int) -> None:
        self.core.prefill_budget = v

    @property
    def healthy(self) -> bool:
        return self.core.healthy

    @healthy.setter
    def healthy(self, v: bool) -> None:
        self.core.healthy = v
