"""The fault-tolerant training driver.

Responsibilities beyond the jitted step:
  * periodic async checkpoints + restore-on-start (checkpoint.py),
  * deterministic data skip-ahead after restore (data.py),
  * straggler watchdog: per-step wall-clock EWMA; steps slower than
    ``straggler_factor`` x the EWMA are logged and counted — on a real
    fleet this signal triggers hot-spare swap; here it drives tests and
    metrics,
  * failure injection hook for the fault-tolerance tests,
  * elastic re-scale: ``Trainer.restore`` accepts a different mesh than the
    checkpoint was written from.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.models.config import ModelConfig
from repro.train import checkpoint
from repro.train.data import SyntheticTokens
from repro.train.optim import Optimizer, adamw
from repro.train.train_step import TrainState, init_state, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    #: ``None`` turns checkpointing (and restore-on-start) off
    checkpoint_dir: Optional[str] = "/tmp/repro_ckpt"
    checkpoint_every: int = 50
    async_checkpoint: bool = True
    log_every: int = 10
    straggler_factor: float = 3.0
    max_steps: int = 200


class Trainer:
    def __init__(self, cfg: ModelConfig, batch: int, seq: int,
                 tcfg: Optional[TrainerConfig] = None,
                 optimizer: Optional[Optimizer] = None,
                 train_step: Optional[Callable] = None,
                 seed: int = 0,
                 failure_injector: Optional[Callable[[int], None]] = None):
        self.cfg = cfg
        self.tcfg = tcfg or TrainerConfig()
        self.optimizer = optimizer or adamw()
        self.data = SyntheticTokens(cfg, batch, seq, seed=seed)
        # the state is donated: the step updates it in place instead of
        # holding two copies of params and moments at once
        self.train_step = train_step or jax.jit(
            make_train_step(cfg, self.optimizer), donate_argnums=0)
        self.state = init_state(cfg, jax.random.PRNGKey(seed),
                                self.optimizer)
        self.failure_injector = failure_injector
        self.step_times: list = []
        self.losses: list = []
        self.straggler_steps: list = []
        self._ckpt_thread = None

    # -- fault tolerance ----------------------------------------------------
    def restore_if_available(self, shardings: Any = None) -> int:
        if self.tcfg.checkpoint_dir is None:
            return 0
        step = checkpoint.latest_step(self.tcfg.checkpoint_dir)
        if step is None:
            return 0
        self.state, step = checkpoint.restore(
            self.tcfg.checkpoint_dir, self.state, step, shardings)
        return int(np.asarray(self.state.step))

    def _maybe_checkpoint(self, step: int, force: bool = False):
        if self.tcfg.checkpoint_dir is None:
            return
        if force or (step > 0 and step % self.tcfg.checkpoint_every == 0):
            if self._ckpt_thread is not None:
                self._ckpt_thread.join()  # one in flight at a time
            self._ckpt_thread = checkpoint.save(
                self.tcfg.checkpoint_dir, step, self.state,
                blocking=not self.tcfg.async_checkpoint)

    # -- main loop -----------------------------------------------------------
    def run(self, n_steps: Optional[int] = None,
            log: Callable[[str], None] = print) -> Dict[str, float]:
        n_steps = n_steps or self.tcfg.max_steps
        start = self.restore_if_available()
        if start:
            log(f"[trainer] restored checkpoint at step {start}")
        ewma = None
        losses = []
        for step in range(start, n_steps):
            if self.failure_injector is not None:
                self.failure_injector(step)  # may raise (simulated crash)
            batch = jax.tree.map(jax.numpy.asarray,
                                 self.data.batch_at(step))
            t0 = time.perf_counter()
            self.state, metrics = self.train_step(self.state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.step_times.append(dt)
            if step == start:
                pass  # first step includes jit compilation; not a baseline
            elif ewma is not None and dt > self.tcfg.straggler_factor * ewma:
                self.straggler_steps.append(step)
                log(f"[trainer] straggler at step {step}: "
                    f"{dt * 1e3:.1f}ms vs EWMA {ewma * 1e3:.1f}ms")
            else:
                ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            loss = float(np.asarray(metrics["loss"]))
            losses.append(loss)
            self.losses.append(loss)
            if step % self.tcfg.log_every == 0:
                log(f"[trainer] step {step} loss {loss:.4f} "
                    f"{dt * 1e3:.1f}ms")
            self._maybe_checkpoint(step + 1)
        self._maybe_checkpoint(n_steps, force=True)
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
        return {"final_loss": losses[-1] if losses else float("nan"),
                "first_loss": losses[0] if losses else float("nan"),
                "mean_step_ms": float(np.mean(self.step_times) * 1e3)
                if self.step_times else float("nan"),
                "stragglers": len(self.straggler_steps)}
