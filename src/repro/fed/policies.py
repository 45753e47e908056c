"""Pluggable async aggregation policies.

A sync round (the paper's rule: barrier on all surviving clients, weighted
average) has no policy: its average is the server reduce
(``fed/aggregate.make_server_reduce``).  The async modes apply arrivals:

  FedAsync     Xie et al.: apply every update the moment it arrives,
               down-weighted by staleness
                   global <- (1 - a_t) * global + a_t * params,
                   a_t = alpha * (1 + staleness) ** -staleness_power.
  FedBuff      Nguyen et al.: buffer K updates (staleness-discounted
               weights), aggregate the buffer, mix with server_lr.

The async engine calls ``on_update`` for every arriving update (in
virtual-time order) and ``on_round_end`` once per round; a policy returns
the possibly updated global tree plus whether it advanced the global model
version (which is what staleness counts).  Policies are orthogonal to how the
client side was compiled (fed/programs.py backends): an update's params
look the same whether the local round ran as a per-client loop or inside
the batched vmap program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp


def _fedavg(trees, weights=None):
    # deferred: repro.core.__init__ re-exports core.gan, which imports
    # fed.engine — a top-level import here would close that cycle and make
    # `import repro.fed` order-dependent
    from repro.core.fedavg import fedavg
    return fedavg(trees, weights)


@dataclass
class ClientUpdate:
    client_id: str
    params: Any                 # decoded (post-codec) discriminator tree
    weight: float               # FedAvg weight (client example count)
    staleness: int = 0          # global versions advanced since download
    recv_time: float = 0.0      # virtual arrival time at the server


def _mix(global_tree, update_tree, rate: float):
    """fp32 convex blend, cast back to the global tree's dtypes."""
    r = jnp.float32(rate)
    return jax.tree.map(
        lambda g, u: ((1.0 - r) * g.astype(jnp.float32)
                      + r * u.astype(jnp.float32)).astype(g.dtype),
        global_tree, update_tree)


class AggregationPolicy:
    """Base: buffer everything, do nothing until told."""
    name = "base"

    def on_update(self, global_tree, up: ClientUpdate
                  ) -> Tuple[Any, bool]:
        return global_tree, False

    def on_round_end(self, global_tree) -> Any:
        return global_tree

    def reset(self) -> None:
        pass


class FedAsync(AggregationPolicy):
    """Staleness-weighted immediate application (FedAsync)."""
    name = "fedasync"

    def __init__(self, alpha: float = 0.6, staleness_power: float = 0.5):
        self.alpha = float(alpha)
        self.staleness_power = float(staleness_power)

    def rate(self, staleness: int) -> float:
        return self.alpha * (1.0 + staleness) ** (-self.staleness_power)

    def on_update(self, global_tree, up: ClientUpdate) -> Tuple[Any, bool]:
        return _mix(global_tree, up.params, self.rate(up.staleness)), True


class FedBuff(AggregationPolicy):
    """Buffered async aggregation: fire once K updates are waiting.

    Buffered updates are weighted by ``weight * (1+staleness)^-power`` and
    averaged; the server blends the buffer mean in at ``server_lr`` (1.0 ==
    replace, the FedBuff default).  A non-empty buffer at round end is
    flushed rather than discarded so no client work is silently dropped.
    """
    name = "fedbuff"

    def __init__(self, buffer_size: int = 2, server_lr: float = 1.0,
                 staleness_power: float = 0.5):
        self.buffer_size = max(1, int(buffer_size))
        self.server_lr = float(server_lr)
        self.staleness_power = float(staleness_power)
        self._buffer: List[ClientUpdate] = []

    def _flush(self, global_tree):
        ws = [u.weight * (1.0 + u.staleness) ** (-self.staleness_power)
              for u in self._buffer]
        mean = _fedavg([u.params for u in self._buffer], ws)
        self._buffer = []
        return _mix(global_tree, mean, self.server_lr)

    def on_update(self, global_tree, up: ClientUpdate) -> Tuple[Any, bool]:
        self._buffer.append(up)
        if len(self._buffer) >= self.buffer_size:
            return self._flush(global_tree), True
        return global_tree, False

    def on_round_end(self, global_tree) -> Any:
        if self._buffer:
            return self._flush(global_tree)
        return global_tree

    def reset(self) -> None:
        self._buffer = []


def make_policy(fed_cfg) -> AggregationPolicy:
    """Factory keyed by ``config.FedConfig.mode`` (the async modes)."""
    if fed_cfg.mode == "fedasync":
        return FedAsync(fed_cfg.fedasync_alpha, fed_cfg.staleness_power)
    if fed_cfg.mode == "fedbuff":
        return FedBuff(fed_cfg.buffer_size,
                       staleness_power=fed_cfg.staleness_power)
    raise ValueError(f"unknown fed mode {fed_cfg.mode!r}")
