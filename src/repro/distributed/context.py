"""Shard context: carries the mesh + axis names into model code.

Model functions (attention, MoE) consult the active ShardCtx to decide whether
to take the distributed code paths (shard_map expert parallelism, seq-sharded
decode attention, sequence-parallel residual constraints).  When no context is
set the model runs the plain single-device path — CPU functional tests and the
serving engine use that.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)   # ("pod","data") on the multi-pod mesh
    model_axis: str = "model"
    seq_parallel: bool = True                 # shard residual-stream seq over model
    ep_mode: str = "gather"                   # MoE dispatch: "gather" (local gather+psum) | "a2a"
    mla_absorb: bool = False                  # weight-absorbed MLA decode (§Perf)
    remat_policy: str = "none"
    unroll: int = 1                           # scan unroll (roofline runs: big int
                                              # => straight-line HLO so cost_analysis
                                              # counts every layer, not the loop body once)
    paired_lg: bool = False                   # gemma2 SSPerf: scan (local, global)
                                              # layer PAIRS with static window flags
                                              # instead of computing both and selecting

    @property
    def dp(self) -> int:
        return int(jax_prod(self.mesh.shape[a] for a in self.batch_axes))

    @property
    def tp(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    def batch_spec(self, *rest) -> P:
        return P(self.batch_axes, *rest)


def jax_prod(it):
    out = 1
    for x in it:
        out *= x
    return out


_state = threading.local()


def current_ctx() -> Optional[ShardCtx]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def shard_ctx(ctx: Optional[ShardCtx]):
    prev = current_ctx()
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev


def divides(n: int, d: int) -> bool:
    return d > 0 and n % d == 0
