"""Trainer: the resumable SPMD training loop.

Ties the pieces together — deterministic sharded data (data/loader.py),
the donated jit train step with TP/FSDP shardings (train/step.py), orbax
checkpointing with exact resume (train/checkpoint.py) — into one loop with
structured-JSON step logs, periodic saves that include the loader cursor,
and crash-resume that replays the identical batch sequence. The reference
has no training at all (SURVEY.md §3.2); this is the rebuild's training
lifecycle, built TPU-first: the jitted step dispatches asynchronously, so
host work (next_batch) overlaps device work, and only logging steps force
a device sync.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

from lambdipy_tpu.utils.logs import get_logger, log_event

log = get_logger("lambdipy.train")


@dataclass
class TrainerConfig:
    total_steps: int
    learning_rate: float = 1e-3
    log_every: int = 10
    ckpt_every: int = 100
    keep_ckpts: int = 3
    fsdp: bool = True
    aux_weight: float = 0.01
    # optimizer stack (train/step.py make_optimizer): global-norm clipping,
    # warmup / cosine decay, gradient accumulation (total_steps counts
    # micro-steps; params update every accum_steps-th step). All default
    # OFF: the defaults must keep the plain-adamw opt_state structure so
    # checkpoints written before these knobs existed still exact-resume.
    grad_clip: float | None = None
    warmup_steps: int = 0
    schedule: str = "constant"  # "constant" | "cosine"
    weight_decay: float = 0.0
    accum_steps: int = 1
    # numerics sanitizer (utils/debug.py): NaN in any step output raises
    # FloatingPointError at the producing primitive. Debug only — forces
    # a device sync per step.
    debug_numerics: bool = False


@dataclass
class TrainerReport:
    steps_run: int
    final_step: int
    resumed_from: int | None
    history: list[dict] = field(default_factory=list)  # logged metric rows


class Trainer:
    """Resumable training over a mesh.

    ``model_apply(params, tokens) -> logits`` (plus optional
    ``model_apply_aux`` for MoE balance losses); ``params`` is the INIT
    pytree — when ``ckpt_dir`` holds a checkpoint, training resumes from
    it instead (same shapes required, enforced by orbax restore).
    """

    def __init__(self, model_apply: Callable, params, mesh, rules, loader,
                 cfg: TrainerConfig, *, ckpt_dir: Path | str | None = None,
                 model_apply_aux: Callable | None = None):
        import jax

        from lambdipy_tpu.train.checkpoint import TrainCheckpointer
        from lambdipy_tpu.train.step import make_optimizer, sharded_train_step

        self.cfg = cfg
        self.mesh = mesh
        self.loader = loader
        self.model_apply = model_apply
        self._jax = jax
        optimizer = make_optimizer(
            cfg.learning_rate, total_steps=cfg.total_steps,
            warmup_steps=cfg.warmup_steps, schedule=cfg.schedule,
            grad_clip=cfg.grad_clip, weight_decay=cfg.weight_decay,
            accum_steps=cfg.accum_steps)
        self.step_fn, self.state, self.batch_sharding = sharded_train_step(
            model_apply, params, mesh, rules,
            learning_rate=cfg.learning_rate, fsdp=cfg.fsdp,
            model_apply_aux=model_apply_aux, aux_weight=cfg.aux_weight,
            optimizer=optimizer)

        self.ckpt: Any = None
        self.resumed_from: int | None = None
        if ckpt_dir is not None:
            self.ckpt = TrainCheckpointer(
                ckpt_dir, max_to_keep=cfg.keep_ckpts,
                save_interval_steps=cfg.ckpt_every)
            restored, at = self.ckpt.restore(
                {"train": self.state, "loader": loader.state_dict()})
            if restored is not None:
                self.state = restored["train"]
                loader.restore(jax.tree_util.tree_map(int, restored["loader"]))
                self.resumed_from = at
                log_event(log, "trainer resumed", step=at)

    @property
    def step(self) -> int:
        """Device-authoritative step counter (forces a sync)."""
        return int(self._jax.device_get(self.state.step))

    def run(self) -> TrainerReport:
        """Train until ``cfg.total_steps`` (absolute, resume-aware)."""
        import contextlib

        from lambdipy_tpu.utils.debug import debug_numerics

        with (debug_numerics() if self.cfg.debug_numerics
              else contextlib.nullcontext()):
            return self._run()

    def _run(self) -> TrainerReport:
        jax = self._jax
        start = self.step
        history: list[dict] = []

        for host_step in range(start + 1, self.cfg.total_steps + 1):
            batch = self.loader.place(self.loader.next_batch(), self.mesh,
                                      self.batch_sharding)
            self.state, metrics = self.step_fn(self.state, batch)
            # the host-side counter mirrors state.step without a sync;
            # metrics are only materialized on logging steps
            if host_step % self.cfg.log_every == 0 or \
                    host_step == self.cfg.total_steps:
                row = {"step": host_step,
                       **{k: round(float(jax.device_get(v)), 5)
                          for k, v in metrics.items()}}
                history.append(row)
                log_event(log, "train step", **row)
            if self.ckpt is not None:
                # CheckpointManager's save_interval_steps decides cadence
                self.ckpt.save(host_step,
                               {"train": self.state,
                                "loader": self.loader.state_dict()})
        if self.ckpt is not None and start < self.cfg.total_steps:
            if self.ckpt.latest_step() != self.cfg.total_steps:
                # final state is always durable, even off-cadence (a
                # cadence save of the same step would collide -> skip)
                self.ckpt.save(self.cfg.total_steps,
                               {"train": self.state,
                                "loader": self.loader.state_dict()}, force=True)
            self.ckpt.wait()
        final = self.step
        return TrainerReport(steps_run=final - start, final_step=final,
                             resumed_from=self.resumed_from, history=history)

    def evaluate(self, eval_loader, *, batches: int = 8) -> float:
        """Mean next-token CE over ``batches`` eval batches (no updates)."""
        import jax

        if not hasattr(self, "_eval_fn"):
            import jax.numpy as jnp

            model_apply = self.model_apply

            # built once (not per evaluate() call — re-tracing would pay a
            # full recompile on every periodic eval)
            @jax.jit
            def eval_loss(params, tokens):
                logits = model_apply(params, tokens[:, :-1])
                targets = tokens[:, 1:]
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                nll = -jnp.take_along_axis(logp, targets[..., None],
                                           axis=-1)[..., 0]
                return jnp.mean(nll)

            self._eval_fn = eval_loss

        total = 0.0
        from lambdipy_tpu.parallel.mesh import use_mesh

        with use_mesh(self.mesh):
            for _ in range(batches):
                batch = eval_loader.place(eval_loader.next_batch(), self.mesh,
                                          self.batch_sharding)
                total += float(jax.device_get(
                    self._eval_fn(self.state.params, batch)))
        return total / batches

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Flush and release the checkpoint manager's background workers."""
        if self.ckpt is not None:
            self.ckpt.wait()
            self.ckpt.close()
            self.ckpt = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
