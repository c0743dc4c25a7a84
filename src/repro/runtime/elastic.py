"""Elastic, fault-tolerant training executor.

Maps CloudCoaster's drain->shutdown discipline onto SPMD training: a
revocation notice (or straggler flag) triggers
    finish current step -> emergency checkpoint -> rebuild the mesh on the
    surviving devices -> reshard the state (Checkpointer restore with new
    shardings) -> continue from the same data-stream position.
Global batch is preserved across rescales — the per-shard batch grows, and
``num_microbatches`` is raised when the larger per-shard batch would not fit.

On real multi-pod deployments the revocation notice arrives from the cloud
provider's metadata service ~30s ahead (paper §3.3); here it is injected via
``preempt_at`` so the whole path is CPU-testable (tests/test_elastic.py
rescales 4 -> 2 devices mid-run and checks loss-curve continuity).

The trainer shares the scheduling layer with the simulators: pass a
``repro.sched.ControllerSpec`` and its ``provisioning_delay`` becomes the
rescale-hysteresis window (in steps) — two fleet changes within one
provisioning window are the add/drain oscillation the §3.2 controller's
projection avoids, so the trainer coalesces them into one.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.data.pipeline import SyntheticBatches
from repro.launch.specs import batch_partition, batch_struct, fix_divisibility
from repro.launch.steps import make_train_step, train_state_specs
from repro.models.decoder import DecoderLM
from repro.optim.adamw import AdamW
from repro.parallel import use_sharding_ctx
from repro.parallel.layouts import layout_rules, param_specs, to_shardings
from repro.runtime.straggler import StragglerWatchdog
from repro.sched.controller import ControllerSpec


def _mesh_from(devices, model_par: int) -> Mesh:
    n = len(devices)
    assert n % model_par == 0
    return Mesh(
        np.asarray(devices).reshape(n // model_par, model_par),
        ("data", "model"))


class ElasticTrainer:
    def __init__(self, model: DecoderLM, opt: AdamW, data: SyntheticBatches,
                 ckpt: Checkpointer, *, model_par: int = 1,
                 devices=None, log: Optional[Callable[[str], None]] = None,
                 spec: Optional[ControllerSpec] = None):
        self.model = model
        self.opt = opt
        self.data = data
        self.ckpt = ckpt
        self.model_par = model_par
        self.devices = list(devices if devices is not None else jax.devices())
        # rescales pick from the devices the trainer was given, never from
        # the process's whole device list
        self._pool = list(self.devices)
        self.log = log or (lambda s: None)
        self.watchdog = StragglerWatchdog()
        self.history = []  # (step, loss, n_devices)
        self.rescales = 0
        self.spec = spec  # hysteresis window = spec.provisioning_delay steps
        self._last_rescale_step: Optional[int] = None
        self._deferred_n_dev: Optional[int] = None
        self.n_coalesced_rescales = 0
        self._build(self.devices)

    # ---------------------------------------------------------------- builds

    def _build(self, devices):
        self.mesh = _mesh_from(devices, self.model_par)
        cfg = self.model.cfg
        self.rules = layout_rules(self.mesh, cfg, "train",
                                  global_batch=self.data.global_batch)
        pspec = param_specs(self.model.init_shape(), self.mesh, self.rules)
        sspec = train_state_specs(pspec, self.opt)
        self.state_shardings = to_shardings(sspec, self.mesh)
        bstruct = batch_struct(cfg, "train", self.data.global_batch,
                               self.data.seq_len)
        bspec = fix_divisibility(
            batch_partition(cfg, "train", self.rules), bstruct, self.mesh)
        self.batch_shardings = to_shardings(bspec, self.mesh)
        step = make_train_step(self.model, self.opt)
        self.step_fn = jax.jit(step, in_shardings=(self.state_shardings,
                                                   self.batch_shardings),
                               out_shardings=(self.state_shardings, None),
                               donate_argnums=(0,))

    def _init_state(self, seed: int):
        with self.mesh, use_sharding_ctx(self.mesh, self.rules):
            params = self.model.init(jax.random.PRNGKey(seed))
            state = self.opt.init_state(params)
            return jax.device_put(state, self.state_shardings)

    # ------------------------------------------------------------------- run

    def _within_hysteresis(self, step: int, n_dev: int) -> bool:
        """Discretionary grows inside one provisioning window are deferred
        (the §3.2 anti-thrash projection); shrinks are revocations and must
        always run."""
        return (self.spec is not None
                and n_dev >= len(self.devices)
                and self._last_rescale_step is not None
                and step - self._last_rescale_step
                < self.spec.provisioning_delay)

    def _plan_rescale(self, step: int, requested: Optional[int]
                      ) -> Optional[int]:
        """Device count to rescale to at this step, or None to hold.

        Grows landing inside the hysteresis window are deferred to the end
        of the window (a newer request — including a shrink, which always
        applies — supersedes a deferred one); they are never dropped."""
        n_dev = requested
        if n_dev is None and self._deferred_n_dev is not None \
                and not self._within_hysteresis(step, self._deferred_n_dev):
            if self._deferred_n_dev != len(self.devices):  # not moot
                n_dev = self._deferred_n_dev
            self._deferred_n_dev = None
        if n_dev is not None and self._within_hysteresis(step, n_dev):
            self._deferred_n_dev = n_dev
            self.n_coalesced_rescales += 1
            self.log(f"rescale to {n_dev} at step {step} deferred "
                     f"(within the provisioning window)")
            return None
        return n_dev

    def _survivors(self, n_dev: int):
        """The first ``n_dev`` of the trainer's own devices: a shrink keeps a
        prefix of the current mesh, a grow re-adds the ones it gave up."""
        if not 1 <= n_dev <= len(self._pool):
            raise ValueError(f"rescale to {n_dev} devices; the trainer was "
                             f"given {len(self._pool)}")
        return self._pool[:n_dev]

    def rescale(self, devices, step: int, state):
        """Drain -> checkpoint -> rebuild mesh -> reshard -> resume."""
        self.log(f"rescale at step {step}: {len(self.devices)} -> "
                 f"{len(devices)} devices")
        self.ckpt.save(step, state, blocking=True)
        self.devices = list(devices)
        self._build(self.devices)
        state, _ = self.ckpt.restore(state, step=step,
                                     shardings=self.state_shardings)
        self.rescales += 1
        return state

    def run(self, total_steps: int, *, seed: int = 0,
            preempt_at: Optional[Dict[int, int]] = None,
            checkpoint_every: int = 50):
        preempt_at = preempt_at or {}
        start = 0
        latest = self.ckpt.latest_step()
        if latest is not None:
            state, start = self.ckpt.restore(
                self._abstract_state(), shardings=self.state_shardings)
            start += 1
            self.log(f"restored checkpoint at step {start - 1}")
        else:
            state = self._init_state(seed)

        for step in range(start, total_steps):
            n_dev = self._plan_rescale(step, preempt_at.get(step))
            if n_dev is not None:
                self._deferred_n_dev = None
                state = self.rescale(self._survivors(n_dev), step, state)
                self._last_rescale_step = step
            batch = jax.device_put(self.data.batch(step), self.batch_shardings)
            t0 = time.perf_counter()
            with self.mesh, use_sharding_ctx(self.mesh, self.rules):
                state, metrics = self.step_fn(state, batch)
            loss = float(metrics["loss"])
            self.watchdog.observe(0, time.perf_counter() - t0)
            self.history.append((step, loss, len(self.devices)))
            if checkpoint_every and step and step % checkpoint_every == 0:
                self.ckpt.save(step, state)
        self.ckpt.save(total_steps - 1, state, blocking=True)
        return state

    def _abstract_state(self):
        """Abstract TrainState for restore-from-cold, eval-shaped through
        the SAME constructor the live path uses (``opt.init_state``) so the
        checkpoint tree cannot drift from the live layout (e.g. int8-moment
        slot trees, error-feedback slots)."""
        return jax.eval_shape(self.opt.init_state, self.model.init_shape())
