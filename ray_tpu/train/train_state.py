"""Sharded train state + jitted training step for the in-tree models.

The reference's per-strategy process-group setup (train/torch/config.py:65
`_setup_torch_process_group`, DDP wrap in train_loop_utils.py:158) collapses on
TPU into ONE jitted function over a named mesh: GSPMD inserts the gradient
psum on the `data`/`fsdp` axes, parameter all-gathers for FSDP, and tensor
collectives for TP.  This module owns that step; trainers (train/),
learners (rl/) and the bench harness all reuse it.

The model is the configuration's: a config dataclass lives in its model's
module (every file under models/ that defines one), and that module offers
`init_params(config, key)`, `logical_axes(config)` and
`loss_fn(params, batch, config)`.  A module may also offer
`loss_and_metrics(params, batch, config) -> (loss, {name: device scalar})`,
whose scalars then ride in the step's metrics (an expert layer's routing
counts), and `not_trained(config)`, a tree of bools like the parameters:
True where a step must leave the leaf as it is.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.models.common import OPTIMIZER, SAVE_ATTN_NAMES
from ray_tpu.ops import dispatch
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    Rules,
    data_sharding,
    tree_shardings,
)
from ray_tpu.train import session
from ray_tpu.util import device_stats, tracing


# what every model's step reports; a model's own metrics ride beside them
_STEP_METRICS = ("loss", "grad_norm", "step")


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10000,
                      b1: float = 0.9, b2: float = 0.95,
                      grad_clip: float = 1.0,
                      mu_dtype=None,
                      nu_dtype=None) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clip — the Llama recipe.

    mu_dtype/nu_dtype=jnp.bfloat16 halve the moment state (down to
    8 B/param with both) — the trade that buys billion-class models
    (and faster remat policies) room in a single chip's HBM."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    if nu_dtype is not None:
        from ray_tpu.train.optim import adamw as lean_adamw

        return optax.chain(
            optax.clip_by_global_norm(grad_clip),
            lean_adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay,
                       mu_dtype=mu_dtype, nu_dtype=nu_dtype),
        )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def _constrain_like_params(tree: Any, params_treedef, param_shardings):
    """Apply param shardings to every params-shaped sub-pytree (optax mu/nu).

    Optimizer state is a nest of (named)tuples whose momentum terms mirror the
    param tree; walking the nest and constraining matching subtrees keeps the
    optimizer sharded FSDP-style with zero per-optimizer knowledge.
    """

    def rec(x):
        try:
            if jax.tree.structure(x) == params_treedef:
                return jax.tree.map(
                    jax.lax.with_sharding_constraint, x, param_shardings)
        except Exception:
            pass
        if hasattr(x, "_fields"):  # NamedTuple
            return type(x)(*[rec(v) for v in x])
        if isinstance(x, tuple):
            return tuple(rec(v) for v in x)
        if isinstance(x, list):
            return [rec(v) for v in x]
        if isinstance(x, dict):
            return {k: rec(v) for k, v in x.items()}
        return x

    return rec(tree)


class ShardedTrainStep:
    """Factory for sharded init/step functions on a mesh, for the model
    whose config it is given (the config's type names the model's module).

    Usage:
        ts = ShardedTrainStep(config, mesh)
        state = ts.init(jax.random.key(0))
        state, metrics = ts.step(state, batch)   # batch: {"tokens": [b, s+1]}

    What a layer's remat keeps under `remat_policy="full"` is decided here,
    by what fits (the other policies do as `models/common.maybe_remat`
    says).  The first `step` lowers and compiles the program that keeps
    each layer's flash out and lse (`SAVE_ATTN_NAMES`: the backward runs no
    attention forward a second time) and reads its `memory_analysis()`,
    arguments + outputs - aliased + temporaries, against the device's
    `bytes_limit` less what the device holds beside the program's own
    arguments (`bytes_in_use` at that moment, the state and the batch taken
    off).  That program is the step if it compiles and fits; where the
    compiler refuses it (RESOURCE_EXHAUSTED) or it does not fit, the step
    is the bare `jax.checkpoint` program: nothing is kept across a LAYER's
    checkpoint.  A model may still keep the two arrays INSIDE a layer's
    backward: on that rung a stream of lanes (`models/latent_moe._layer`)
    puts attention under a checkpoint of its own, which keeps out and lse
    from the layer's forward made again to attention's backward, one
    layer's pair at a time, so that the rung runs the flash forward twice a
    layer and not three times
    (`dispatch.taken()["latent_moe.attention_checkpoint"]`).  The reading
    is the least limit
    and the most in use over this process's devices of the mesh, and the
    decision is ONE for the mesh's processes: all keep, or none does.  A
    backend with no `bytes_limit` (the CPU) takes the first program:
    nothing there can refuse it.  A caller who holds other arrays on the
    device (a reference copy, an EMA) puts them there BEFORE the first
    step, so that the decision sees them: what comes later has the room
    the chosen program leaves.  A `loss_fn` of the caller's is not the
    model's to rebuild: it runs as it is given.  Nothing is remembered between
    starts: a first program that compiled is found in the persistent
    compilation cache, one the compiler refuses is refused again.  What was
    decided is in `dispatch.taken()["train.remat"]` and on the first
    `train.step` span; `device_stats.program_report("train.step")` reports
    the program that runs.
    """

    def __init__(self, config, mesh,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 rules: Rules = DEFAULT_RULES,
                 loss_fn: Optional[Callable] = None,
                 num_microbatches: Optional[int] = None):
        self.config = config
        self.model = model = importlib.import_module(type(config).__module__)
        self._evaluators: Dict[str, Any] = {}
        self.mesh = mesh
        self.optimizer = optimizer or default_optimizer()
        self.rules = rules
        # Pipeline parallelism: a stage axis >1 in the mesh routes the
        # loss through the GPipe-pipelined forward (greenfield vs the
        # reference — Ray ships no in-tree PP, SURVEY.md §2.4).  Params
        # keep their [L, ...] layout; the layers->stage rule shards the
        # layer dim so each device already holds its stage's run.
        self.num_stages = int(dict(mesh.shape).get("stage", 1))
        self.num_microbatches = num_microbatches
        # the model's own step metrics ride where it has some and the
        # loss is its
        self.loss_fn, self._loss_and_metrics = (
            (loss_fn, None) if loss_fn is not None
            else self._model_losses(config))
        # the ladder's first rung, where the ladder applies; which rung
        # runs (None: the first step decides)
        self._kept_config = None
        if loss_fn is None and config.remat and config.remat_policy == "full":
            self._kept_config = dataclasses.replace(
                config, remat_policy="save_attn")
        self._keep: Optional[bool] = None if self._kept_config else False
        self._not_trained = (model.not_trained(config)
                             if hasattr(model, "not_trained") else None)
        self.param_logical = model.logical_axes(config)
        self.param_shardings = tree_shardings(
            mesh, self.param_logical, rules)
        self.batch_sharding = data_sharding(mesh)
        self._params_treedef = jax.tree.structure(self.param_shardings)

        self._init = device_stats.count_compiles(
            jax.jit(self._init_fn), "train.init")
        self._step = device_stats.count_compiles(
            jax.jit(self._step_fn, donate_argnums=(0,),
                    static_argnames=("keep",)), "train.step")
        self._spanned: set = set()
        self._steps = 0
        self._last_loss = None      # the step ledger's `device_dry` asks it

    def _model_losses(self, config):
        """(loss, loss with the model's own metrics or None) of the model
        under `config`, each a function of (params, batch)."""
        model = self.model
        if self.num_stages > 1:
            return (lambda p, b: model.loss_fn_pipelined(
                p, b, config, self.num_stages, self.num_microbatches,
                mesh=self.mesh)), None
        return (lambda p, b: model.loss_fn(p, b, config)), (
            (lambda p, b: model.loss_and_metrics(p, b, config))
            if hasattr(model, "loss_and_metrics") else None)

    def _span(self, name: str, attrs: Optional[Dict[str, Any]] = None,
              force: bool = False, entered: Optional[list] = None):
        """Host time to place the inputs and enqueue one program.  The
        FIRST call of each program holds its compile or cache load and is
        recorded whatever the tracing flag says (the start-up timeline
        reads it), as is one the caller forces; later ones follow the
        flag, and a running profile."""
        first = name not in self._spanned
        self._spanned.add(name)
        return tracing.trace_span(name, attrs, force=first or force,
                                  entered=entered)

    # -- init ---------------------------------------------------------------
    def _init_fn(self, rng):
        params = self.model.init_params(self.config, rng)
        params = jax.tree.map(
            jax.lax.with_sharding_constraint, params, self.param_shardings)
        opt_state = self.optimizer.init(params)
        opt_state = _constrain_like_params(
            opt_state, self._params_treedef, self.param_shardings)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    def _mesh_scope(self):
        """The mesh as the ambient abstract mesh: activation constraints
        by logical name, ring attention's "auto" and the flash kernel's
        shard_map all read jax.sharding.get_abstract_mesh(), which a
        bare `with mesh:` no longer sets."""
        return jax.sharding.set_mesh(self.mesh)

    def init(self, rng):
        with self._span("train.init"), self._mesh_scope():
            return self._init(rng)

    # -- step ---------------------------------------------------------------
    def _step_fn(self, state, batch, keep: bool = False):
        """keep: the ladder's first rung, each layer's flash out and lse
        held across its checkpoint (static: one jitted function, two
        programs)."""
        loss_fn, loss_and_metrics = (
            self._model_losses(self._kept_config) if keep
            else (self.loss_fn, self._loss_and_metrics))
        model_metrics = {}
        if loss_and_metrics is None:
            loss_val, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch))(state["params"])
        else:
            (loss_val, model_metrics), grads = jax.value_and_grad(
                lambda p: loss_and_metrics(p, batch),
                has_aux=True)(state["params"])
        # everything behind the gradient is one part of the step
        # (models/common.py's vocabulary): constraint and norm, clip, AdamW,
        # apply_updates and the parameters' casts
        with jax.named_scope(OPTIMIZER):
            grads = jax.tree.map(
                jax.lax.with_sharding_constraint, grads,
                self.param_shardings)
            updates, opt_state = self.optimizer.update(
                grads, state["opt_state"], state["params"])
            if self._not_trained is not None:   # weight decay moves them too
                updates = jax.tree.map(
                    lambda u, frozen: jnp.zeros_like(u) if frozen else u,
                    updates, self._not_trained)
            params = optax.apply_updates(state["params"], updates)
            params = jax.tree.map(
                jax.lax.with_sharding_constraint, params,
                self.param_shardings)
            grad_norm = optax.global_norm(grads).astype(jnp.float32)
        metrics = {
            "loss": loss_val.astype(jnp.float32),
            "grad_norm": grad_norm,
            "step": state["step"] + 1,
            **model_metrics,
        }
        return {"params": params, "opt_state": opt_state,
                "step": state["step"] + 1}, metrics

    def _choose_rung(self, state, batch, attrs: Dict[str, Any]) -> bool:
        """Whether the step keeps out and lse (the class docstring's ladder),
        decided inside the first step's span, whose attributes get the
        record.  The compile made here is the one the call that follows
        finds, so a first rung that fits costs the one compile, or the one
        load from the persistent cache, that a first step always cost."""
        # of this process's devices of the mesh: the least limit, and the
        # most in use now, before the program is loaded
        stats = [s for s in map(device_stats.memory_stats,
                                self.mesh.local_devices) if s]
        limit = min((s["bytes_limit"] for s in stats if "bytes_limit" in s),
                    default=None)
        in_use = max((s.get("bytes_in_use", 0) for s in stats), default=0)
        total = beside = None
        try:
            memory = self._step.lower(
                state, batch, keep=True).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            keep = False
        else:
            total = device_stats.program_bytes(memory)
            if total is not None:
                # what the chip holds beside the program's own arguments
                # (the state and the batch are on it already)
                beside = max(0, in_use - int(memory.argument_size_in_bytes))
            keep = limit is None or total is None or total + beside <= limit
        keep = self._everywhere(keep)
        if keep and total is not None:
            # the program that will run, temporaries and all: the HBM
            # watermark's, which the allocator's peak leaves them out of
            device_stats.note_program(total + beside)
        kept = "kept:" + (",".join(SAVE_ATTN_NAMES) if keep else "none")
        # the bytes are the FIRST rung's: what was held against the limit
        attrs.update(remat=kept, remat_program_bytes=total,
                     remat_beside_bytes=beside, bytes_limit=limit)
        dispatch.record("train.remat",
                        f"{kept},program{total}of{limit},beside{beside}")
        return keep

    def _everywhere(self, keep: bool) -> bool:
        """One decision for the whole job: the mesh's processes run ONE
        program, so all keep or none does (limits differ between chips,
        and what else a chip holds between processes)."""
        if len({d.process_index for d in self.mesh.devices.flat}) == 1:
            return keep
        along = jax.sharding.PartitionSpec(self.mesh.axis_names)
        flags = jax.make_array_from_callback(
            (self.mesh.size,), jax.sharding.NamedSharding(self.mesh, along),
            lambda index: np.full((1,), keep))
        return bool(jax.jit(jnp.all, out_shardings=jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()))(flags))

    def step(self, state, batch):
        self._steps += 1
        attrs = {"step": self._steps}   # kept by identity: see trace_span
        # A model's own metrics ride on the spans of steps 1, 2, 4, 8, ...,
        # which are recorded whatever the tracing flag says, so that
        # timeline.json holds them through a run and not for its first step
        # alone (a routing count drifts as the weights move).  Reading them
        # waits for that step: a few scalars, a logarithm of the run's
        # steps times.
        counted = (self._loss_and_metrics is not None
                   and self._steps & (self._steps - 1) == 0)
        # The step ledger's row (`session.StepLedger`), every step: the
        # span's own clock reading, the block's seconds, and whether the
        # last step's loss was ready already (asking never waits).
        flags = session.STEP_SYNCED if counted else 0
        if self._last_loss is not None and self._last_loss.is_ready():
            flags |= session.STEP_DEVICE_DRY
        entered: list = []
        with self._span("train.step", attrs, force=counted, entered=entered):
            t = time.perf_counter()
            ledger = session.step_ledger
            row = ledger.enter(self._steps, entered[0], flags | (
                session.STEP_PROFILED if entered[1] else 0))
            batch = jax.device_put(batch, self.batch_sharding)
            with self._mesh_scope():
                if self._keep is None:
                    self._keep = self._choose_rung(state, batch, attrs)
                state, metrics = self._step(state, batch, keep=self._keep)
            if counted:
                attrs.update({k: float(v) for k, v in metrics.items()
                              if k not in _STEP_METRICS})
            self._last_loss = metrics["loss"]
            ledger.dispatched(row, time.perf_counter() - t)
            return state, metrics

    # -- eval ----------------------------------------------------------------
    @functools.cached_property
    def _eval(self):
        def eval_fn(params, batch):
            return self.loss_fn(params, batch).astype(jnp.float32)

        return device_stats.count_compiles(jax.jit(eval_fn), "train.eval")

    def _evaluator(self, name: str):
        if name not in self._evaluators:
            fn = getattr(self.model, name)
            self._evaluators[name] = device_stats.count_compiles(
                jax.jit(lambda params, batch: fn(params, batch, self.config)),
                "train.eval")
        return self._evaluators[name]

    def eval_step(self, params, batch, output: Optional[str] = None):
        """The loss of `batch`; or, with `output`, what the model module's
        function of that name gives for (params, batch, config), e.g. a
        model's `token_nll`: the same sharded forward-only program."""
        program = self._eval if output is None else self._evaluator(output)
        with self._span("train.eval"):
            batch = jax.device_put(batch, self.batch_sharding)
            with self._mesh_scope():
                return program(params, batch)
