"""Trainer abstraction + the local (single-process) JAX trainer.

Reference counterpart: the Trainer ABC and eager/`tf.function` training paths
(/root/reference/elasticdl/python/worker/trainer.py:17-56,
worker/ps_trainer.py:388-401). TPU-first redesign: the step is a pure jitted
function over an explicit (variables, opt_state) pytree — XLA fuses the
forward, backward and optimizer update into one program, and the same step
function is reused by the AllReduce trainer under shard_map.
"""

import functools
from abc import ABC, abstractmethod

import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import datapath

logger = get_logger("worker.trainer")


class Trainer(ABC):
    """What the worker loop needs from any training strategy."""

    @abstractmethod
    def init_variables_if_needed(self, features):
        ...

    @abstractmethod
    def train_minibatch(self, features, labels):
        """Returns (accepted: bool, model_version: int, loss).

        `loss` is a float-convertible scalar. On-device strategies return a
        lazy jax array so the host never blocks on the step; callers must
        only materialize it (float()) when they actually log it, keeping
        steps dispatch-ahead on TPU."""

    @abstractmethod
    def evaluate_minibatch(self, features, model_version=-1):
        """Forward pass; returns model outputs (numpy)."""

    def predict_minibatch(self, features):
        return self.evaluate_minibatch(features)

    @abstractmethod
    def get_model_version(self) -> int:
        ...

    def export_variables(self):
        """Checkpointable state; override where meaningful."""
        return None


def _to_device_batch(features):
    """numpy batch (array or dict pytree) -> jnp arrays."""
    return jax.tree_util.tree_map(jnp.asarray, features)


# A model's training output may carry a small pytree of statistics under
# this key. The step then returns {"loss", "stats"} where it returns the
# loss (one more output of the same program: no launch, no fence), and
# `split_stats` takes it apart on the host. A model without the key gets
# the bare loss and the step program it always had.
STATS_KEY = "stats"


def with_stats(loss, stats):
    return loss if stats is None else {"loss": loss, STATS_KEY: stats}


def split_stats(step_loss):
    """(loss, stats or None) of what a step returned as its loss."""
    if isinstance(step_loss, dict):
        return step_loss["loss"], step_loss[STATS_KEY]
    return step_loss, None


class JaxTrainer(Trainer):
    """Shared JAX machinery: lazy variable init, jitted train/forward steps.

    Subclasses override `_build_train_step` / `_build_forward` to insert
    collectives (AllReduce) or parameter-exchange hooks (PS).
    """

    def __init__(self, model, loss_fn, optimizer_spec, seed=0):
        # Persistent compilation cache (recompile-free elasticity):
        # wired before the first jit so even bare trainers (tests,
        # benches) rehydrate executables when the knob names a dir.
        from elasticdl_tpu.common.compile_cache import (
            ensure_compile_cache,
        )

        ensure_compile_cache()
        self._model = model
        self._loss_fn = loss_fn
        self._optimizer_spec = optimizer_spec
        self._optax = optimizer_spec.to_optax()
        self._rng = jax.random.PRNGKey(seed)
        self._variables = None
        self._opt_state = None
        self._version = 0
        self._train_step = None
        self._forward = None
        # Device arrays of the newest step's model statistics, or None;
        # ready once that step's loss has been read.
        self.last_step_stats = None
        # Checkpoint path to restore from right after lazy init (worker-side
        # resume for strategies whose state lives in the worker).
        self.restore_on_init = None
        # Step-phase breakdown, reported per task at DEBUG by the worker
        # loop (reference timing_utils.py usage in ps_trainer/worker).
        from elasticdl_tpu.common.timing import Timing

        self.timing = Timing()
        # Per-step MFU estimate (observability/mfu.py): FLOPs from the
        # jitted step's cost analysis, period from successive steps.
        from elasticdl_tpu.observability.mfu import StepCostModel

        self.step_cost = StepCostModel()

    # ---------- init ----------

    def init_variables_if_needed(self, features):
        if self._variables is not None:
            return
        self._rng, init_rng = jax.random.split(self._rng)
        device_features = _to_device_batch(features)
        # One program, not an eager operation a tensor: a model of some
        # hundred tensors took tens of seconds of set-up eagerly, and the
        # program is served from the compile cache by the next job.
        from elasticdl_tpu.observability.profiling import tracked_jit

        variables = tracked_jit(
            lambda rng, feats: self._model.init(
                {"params": rng, "dropout": rng}, feats, training=False
            ),
            name="model_init",
        )(init_rng, device_features)
        self._variables = jax.tree_util.tree_map(jnp.asarray, dict(variables))
        self._opt_state = self._optax.init(self._variables["params"])
        n_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(self._variables["params"])
        )
        logger.info("Initialized model with %d parameters", n_params)
        self._train_step = self._build_train_step()
        self._forward = self._build_forward()
        if self.restore_on_init:
            from elasticdl_tpu.common.save_utils import (
                restore_trainer_checkpoint,
            )

            path, self.restore_on_init = self.restore_on_init, None
            restore_trainer_checkpoint(self, path)

    # ---------- step functions ----------

    def _apply_train(self, params, state, rng, features, labels,
                     slice_to=None, model=None):
        """Pure fwd+bwd; the body every strategy shares. slice_to trims
        padding rows off outputs/labels before the loss (used by sharded
        strategies that pad batches to the mesh size). `model` overrides
        self._model for strategies that train through a mesh-bound
        variant of the same architecture (e.g. ring-attention SP) whose
        param tree is identical."""
        mutable = [k for k in state]
        model = model if model is not None else self._model

        def loss_of(p):
            out = model.apply(
                {"params": p, **state},
                features,
                training=True,
                rngs={"dropout": rng},
                mutable=mutable if mutable else False,
            )
            outputs, new_state = out if mutable else (out, state)
            # What the model reports of its step (a routed layer's
            # counts): handed back beside the loss, never differentiated.
            stats = None
            if isinstance(outputs, dict) and STATS_KEY in outputs:
                outputs = dict(outputs)
                stats = jax.lax.stop_gradient(outputs.pop(STATS_KEY))
            labels_real = labels
            if slice_to is not None:
                # Only leaves carrying the batch dim get sliced back to
                # the real rows (bit-identical CE vs single-device).
                # Reduced scalars a model emits (e.g. a MoE aux loss) WERE
                # computed over the padded batch; padding is cyclic
                # repetition of real rows, so such regularizers are
                # marginally reweighted on a task's final partial
                # minibatch — same semantics as the multi-host ragged
                # batch documented in the AllReduce trainer.
                batch_n = jax.tree_util.tree_leaves(features)[0].shape[0]

                def trim(o):
                    if getattr(o, "ndim", 0) >= 1 and o.shape[0] == batch_n:
                        return o[:slice_to]
                    return o

                outputs = jax.tree_util.tree_map(trim, outputs)
                labels_real = jax.tree_util.tree_map(trim, labels)
            return self._loss_fn(labels_real, outputs), (new_state, stats)

        (loss, (new_state, stats)), grads = jax.value_and_grad(
            loss_of, has_aux=True
        )(params)
        return with_stats(loss, stats), grads, new_state

    def _step_body(self, variables, opt_state, rng, features, labels,
                   slice_to=None, model=None, update_apart=False):
        """fwd + bwd + optimizer update; shared by every on-device-update
        strategy (local and AllReduce).

        `update_apart` keeps the optimizer's update out of the fusions
        that end in a weight-gradient product. With nothing between the
        backward and the update the TPU compiler pulls Adam's elementwise
        work over three more float32 operands into each product's output
        fusion, and the product then runs at 53 to 84% of its roof where
        it reaches 93 to 97% alone (PERF.md section 6, PR 31). The same
        values reach the update either way; only the fusion boundary
        moves. One barrier a leaf, never one over the tree: that would
        hold every gradient live at once. The step's builder says when
        (`step_plan.update_apart_for`)."""
        params = variables["params"]
        state = {k: v for k, v in variables.items() if k != "params"}
        loss, grads, new_state = self._apply_train(
            params, state, rng, features, labels, slice_to, model=model
        )
        if update_apart:
            grads = jax.tree_util.tree_map(
                jax.lax.optimization_barrier, grads
            )
        updates, new_opt_state = self._optax.update(
            grads, opt_state, params
        )
        new_params = optax.apply_updates(params, updates)
        return {"params": new_params, **new_state}, new_opt_state, loss

    def _build_train_step(self):
        # tracked_jit (observability/profiling.py): every lowering is
        # counted/timed with its cause attributed (cold / shape_change /
        # mesh_change / donation_miss). key_argnums keeps the hot-path
        # shape signature on the batch — param shapes are static after
        # init, and flattening the full tree per step is the cost the
        # MFU cache already refused to pay.
        from elasticdl_tpu.observability.profiling import tracked_jit

        # One device, no reduction between the backward and the update:
        # the update is compiled apart from the weight-gradient products
        # (`_step_body`), and the step's compile event says so.
        return tracked_jit(
            functools.partial(self._step_body, update_apart=True),
            name="train_step", key_argnums=(3, 4),
            event_fields={"update_apart": True},
            donate_argnums=(0, 1), first_call="setup.first_dispatch",
        )

    def _build_forward(self):
        from elasticdl_tpu.observability.profiling import tracked_jit

        def forward(variables, features):
            return self._model.apply(variables, features, training=False)

        return tracked_jit(forward, name="forward", key_argnums=(1,))

    # ---------- Trainer interface ----------

    def train_minibatch(self, features, labels):
        self.init_variables_if_needed(features)
        self._rng, step_rng = jax.random.split(self._rng)
        with datapath.get().stage("h2d", timing=self.timing):
            device_features = _to_device_batch(features)
            device_labels = _to_device_batch(labels)
        step_args = (
            self._variables,
            self._opt_state,
            step_rng,
            device_features,
            device_labels,
        )
        # Keyed on the batch only: param shapes are static after init.
        self.step_cost.observe(
            self._train_step, step_args, key_args=step_args[3:]
        )
        self._variables, self._opt_state, loss = self._train_step(
            *step_args
        )
        self._last_batch = step_args[3:]
        loss, self.last_step_stats = split_stats(loss)
        self._version += 1
        # Lazy device scalar: converting to float here would block the host
        # on every step and serialize dispatch (the round-1 bench ceiling).
        return True, self._version, loss

    # The newest step's (features, labels) on the device: their shapes are
    # what a map of the step's scopes is lowered with. Down here, not in
    # the constructor: the lines above the step's call are in the compile
    # cache's key (`profiling.open_compile`).
    _last_batch = None

    def step_for_scopes(self):
        """(the training step, the shapes of its arguments, the context it
        is called in: none) for a map of its scopes
        (`observability/step_scopes.py`), or None before the first step.
        Shapes, never the live buffers: those are donated."""
        from elasticdl_tpu.observability.step_scopes import abstract_of

        if self._last_batch is None:
            return None
        return self._train_step, abstract_of(
            (self._variables, self._opt_state, self._rng)
            + tuple(self._last_batch)), None

    def evaluate_minibatch(self, features, model_version=-1):
        self.init_variables_if_needed(features)
        outputs = self._forward(self._variables, _to_device_batch(features))
        # Multi-output models return pytrees; hand numpy back either way.
        return jax.tree_util.tree_map(np.asarray, outputs)

    def get_model_version(self):
        return self._version

    def export_variables(self):
        return {
            "variables": jax.device_get(self._variables),
            # Left as device arrays: callers that persist it (the saver)
            # materialize per leaf; callers that only need the structure
            # or discard it (weights-only export, restore template) skip
            # a 2x-model-size device-to-host copy.
            "opt_state": self._opt_state,
            "rng": np.asarray(self._rng),
            "version": self._version,
        }

    def restore_variables(self, exported):
        self._variables = jax.tree_util.tree_map(
            jnp.asarray, exported["variables"]
        )
        if exported.get("opt_state") is not None:
            self._opt_state = jax.tree_util.tree_map(
                jnp.asarray, exported["opt_state"]
            )
        else:
            # Pre-round-3 checkpoints carried weights only; resuming from
            # one resets the optimizer moments (the old, lossy behavior).
            logger.warning(
                "Checkpoint has no optimizer state; re-initializing it"
            )
            self._opt_state = self._optax.init(self._variables["params"])
        if exported.get("rng") is not None:
            self._rng = jnp.asarray(exported["rng"])
        self._version = exported["version"]
        self._train_step = self._build_train_step()
        self._forward = self._build_forward()


class LocalTrainer(JaxTrainer):
    """Single-chip training: the minimum end-to-end strategy (reference
    DistributionStrategy.LOCAL)."""

    def init_variables_if_needed(self, features):
        if self._variables is not None:
            return
        # Down here, below the step's own functions: jax keeps the line
        # numbers of a traced call stack in the compile cache's key.
        from elasticdl_tpu.observability import tracing

        with tracing.span("setup.model_init", cat=tracing.SETUP):
            super().init_variables_if_needed(features)
