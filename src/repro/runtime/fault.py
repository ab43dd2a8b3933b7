"""Fault tolerance: checkpoint/restart training loop, straggler + heartbeat
machinery (DESIGN.md §6).

What is *executable* here (and tested on CPU):
  * ``FaultTolerantLoop`` — drives train steps; checkpoints every
    ``ckpt_every`` (async); on a step exception it restores the latest
    complete checkpoint, regenerates the batch from the stateless pipeline
    (data order is a function of step, nothing to rewind), and retries up
    to ``max_restarts`` times.  Tests inject failures and assert bit-exact
    convergence with the uninterrupted run.
  * ``HeartbeatRegistry`` (defined in ``core.health``, re-exported) — host
    liveness bookkeeping with deadlines; a missed heartbeat marks the host
    suspect and fires a callback (the hook a real deployment wires to its
    scheduler for pod replacement).
  * ``StragglerMonitor`` — per-step wall-time EWMA; steps slower than
    ``threshold ×`` the EWMA are recorded as straggler events (the signal
    used for hot-spare promotion at fleet scale — promotion itself needs a
    scheduler, so it ends at the callback boundary here, documented).

What is documented-only (needs >1 real host): coordinated restart across
hosts (jax.distributed barrier) and spare-pod promotion.  The code paths
end at explicit callbacks so a deployment can graft its control plane on.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax

from ..ckpt import CheckpointManager
from ..core.health import CircuitBreaker, HeartbeatRegistry, OverloadSchedule

__all__ = [
    "CircuitBreaker",
    "HeartbeatRegistry",
    "OverloadSchedule",
    "StragglerMonitor",
    "FaultTolerantLoop",
]


class StragglerMonitor:
    """EWMA step-time tracker; flags outlier steps."""

    def __init__(self, threshold: float = 2.0, alpha: float = 0.2,
                 on_straggler: Optional[Callable[[int, float, float], None]] = None):
        self.threshold = threshold
        self.alpha = alpha
        self.ewma: Optional[float] = None
        self.events: list[tuple[int, float, float]] = []
        self.on_straggler = on_straggler

    def record(self, step: int, dt: float) -> bool:
        is_straggler = False
        if self.ewma is not None and dt > self.threshold * self.ewma:
            self.events.append((step, dt, self.ewma))
            is_straggler = True
            if self.on_straggler:
                self.on_straggler(step, dt, self.ewma)
            # Do not fold outliers into the baseline.
        else:
            self.ewma = dt if self.ewma is None else (1 - self.alpha) * self.ewma + self.alpha * dt
        return is_straggler


@dataclasses.dataclass
class FaultTolerantLoop:
    """Checkpoint/restart driver around a (state, batch) -> (state, metrics)
    step function and a stateless batch source ``batch_fn(step)``."""

    step_fn: Callable[[Any, dict], tuple[Any, dict]]
    batch_fn: Callable[[int], dict]
    ckpt: CheckpointManager
    ckpt_every: int = 10
    max_restarts: int = 3
    straggler: Optional[StragglerMonitor] = None

    def run(self, state: Any, start_step: int, num_steps: int) -> tuple[Any, list[dict]]:
        history: list[dict] = []
        step = start_step
        restarts = 0
        abstract = jax.tree.map(lambda x: x, state)  # structure template
        while step < start_step + num_steps:
            try:
                t0 = time.perf_counter()
                batch = self.batch_fn(step)
                state, metrics = self.step_fn(state, batch)
                dt = time.perf_counter() - t0
                if self.straggler is not None:
                    self.straggler.record(step, dt)
                history.append({"step": step, **{k: float(v) for k, v in metrics.items()}})
                step += 1
                if step % self.ckpt_every == 0:
                    self.ckpt.save(step, state)
            except (FileNotFoundError, KeyboardInterrupt):
                raise
            except Exception:
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                latest = self.ckpt.latest()
                if latest is None:
                    # No checkpoint yet: restart from the caller's state.
                    step = start_step
                    continue
                step, state = self.ckpt.restore(abstract, latest)
        self.ckpt.wait()
        return state, history
