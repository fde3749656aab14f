"""Per-round training engine.

Replaces the reference's ``Strategy.train`` / ``parallel_train_fn`` /
``_train`` / ``validation_and_early_stopping`` stack
(src/query_strategies/strategy.py:249-442).  Key differences by design:

  * ONE persistent JAX runtime for the whole experiment — no per-round
    ``mp.spawn`` + NCCL process-group setup (strategy.py:288-315).  The
    mesh exists once; each round just re-runs the jitted step.
  * The train step is a single jitted function over a data-sharded batch:
    gradient psum (DDP allreduce, strategy.py:336), global-batch BN stats
    (SyncBatchNorm, strategy.py:292), and the fused normalize/augment all
    come out of XLA's partitioner.
  * BN-freeze semantics preserved: the reference trains with the network in
    eval() mode whenever features are frozen OR a pretrained checkpoint is
    configured (strategy.py:366-367) — here ``train_bn=False`` selects
    running-average BN with no stats update while gradients still flow.
  * Early stopping keeps the best parameters both on disk (best_rd_{n},
    strategy.py:425-430) and in memory.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import serialization, struct

from .. import faults
from ..config import TrainConfig
from ..data.augment import apply_view
from ..faults import preempt as preempt_lib
from ..telemetry import runtime as tele_runtime
from ..telemetry import spans as tele_spans
from ..data.core import Dataset, rows_are_tokens
from ..models import backbone
from ..data.pipeline import (batch_index_lists, iterate_batches,
                             num_batches, padded_batch_layout,
                             train_feed_batches)
from ..parallel import mesh as mesh_lib
from ..utils.logging import get_logger
from . import checkpoint as ckpt_lib
from .evaluation import accumulate_metrics, make_eval_step
from .optim import make_fused_optimizer, make_lr_schedule, make_optimizer


# Checkpoint IO under the ONE retry policy (DESIGN.md §10): a transient
# write failure (full-for-a-moment disk, NFS hiccup, injected
# ckpt_write fault) retries with backoff instead of killing the fit —
# every write here is atomic (tmp + rename), so a retried call simply
# re-runs the whole publish and the pair lands consistent.
_CKPT_RETRY = faults.RetryPolicy(site="ckpt_write",
                                 classify=faults.classify_exception,
                                 max_attempts=3)

# Registered step-builders (scripts/al_lint.py recompile-hazard): every
# jax.jit in this module lives inside one of these — the zero-recompile
# warm-round invariant (tests/test_compile_reuse.py) is only auditable
# when the set of compile sites is enumerable.
_STEP_BUILDERS = ("_build_train_step", "_build_train_step_int8",
                  "_build_chained_train_step",
                  "_build_resident_batch_step", "_build_epoch_scan",
                  "reinit_optimizer")

# Donating callables stored on attributes (al_lint donation-safety):
# attribute name -> donate_argnums of the underlying jitted step.  Every
# non-traced call site must rebind the donated argument from the result
# in the same statement (``state, ... = self._train_step(state, ...)``)
# or the lint flags a use-after-donate of the deleted buffer — the bug
# class reinit_optimizer's out_shardings/zeroing work dodged by hand in
# PR 9.
_DONATES = {"_train_step": (0,),
            "_chained_train_step": (0, 2),
            "_resident_batch_step": (0, 5),
            "_epoch_scan": (0,),
            "_reinit_opt": (0,)}


class TrainState(struct.PyTreeNode):
    """``params`` are the leaves a fit moves; ``frozen`` the leaves it
    never does (models/backbone.py): the optimizer state, the gradients,
    every snapshot and every checkpoint file are shaped like ``params``.
    ``counters``: the model's ``row_counters`` summed over the fit's real
    rows so far, on the device."""
    params: Any
    batch_stats: Any
    opt_state: Any
    step: jnp.ndarray
    frozen: Any = struct.field(default_factory=dict)
    counters: Any = struct.field(default_factory=dict)

    @property
    def variables(self) -> Dict[str, Any]:
        """What ``model.apply`` reads: the trainable leaves over the
        frozen ones (a new dict over the same arrays)."""
        return {"params": backbone.merge_params(self.params, self.frozen),
                "batch_stats": self.batch_stats}

    @property
    def trainable_variables(self) -> Dict[str, Any]:
        """What a snapshot, a checkpoint file or the best-epoch copy
        holds: no frozen leaf."""
        return {"params": self.params, "batch_stats": self.batch_stats}


def full_variables(trainable: Dict[str, Any], frozen: Any) -> Dict[str, Any]:
    """``trainable_variables`` (or a checkpoint's tree) -> the tree
    ``model.apply`` reads."""
    return {**trainable,
            "params": backbone.merge_params(trainable["params"], frozen)}


def frozen_beside(fn: Callable) -> Callable:
    """``fn(state, ...) -> (state, ...)`` as ``step(state, frozen, ...)``:
    the state's frozen leaves in an argument of their own, so that a
    ``jax.jit`` which donates the state does not donate them.  The
    program keeps ``fn``'s name."""
    def step(state, frozen, *args, **kw):
        out = fn(state.replace(frozen=frozen), *args, **kw)
        return (out[0].replace(frozen={}),) + tuple(out[1:])
    step.__name__ = step.__qualname__ = fn.__name__
    return step


class StateStep:
    """A jitted ``frozen_beside`` step behind the signature it had before
    the split, ``step(state, ...)``: the state is donated and its frozen
    leaves are not, so the arrays a strategy loaded once are the arrays
    every later state holds.  Called, lowered and counted like the
    ``jax.jit`` object it wraps."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.__name__ = getattr(jitted, "__name__", "step")

    def __call__(self, state, *args, **kw):
        frozen = state.frozen
        out = self.jitted(state.replace(frozen={}), frozen, *args, **kw)
        return (out[0].replace(frozen=frozen),) + tuple(out[1:])

    def lower(self, state, *args, **kw):
        return self.jitted.lower(state.replace(frozen={}), state.frozen,
                                 *args, **kw)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()


@dataclasses.dataclass
class FitResult:
    """``best_variables``: the best epoch's trainable tree where THIS fit
    computed it, on the device (the final state's own leaves when the
    best is the last epoch without validation, else the copy taken at
    the best epoch); None when it is only in ``best_ckpt`` (a mid-round
    resume whose best epoch ran in an earlier process).  ``best_host``:
    the one host copy of it, fetched for ``best_ckpt``; None when this
    process wrote no file."""
    state: TrainState
    best_epoch: int
    best_perf: float
    epochs_run: int
    history: List[Dict[str, float]]
    best_variables: Optional[Dict[str, Any]] = None
    best_host: Optional[Dict[str, Any]] = None


class _Published(NamedTuple):
    """What ``ckpt/publish_best`` fetched and wrote: the best epoch, its
    host tree and the file's bytes."""
    epoch: int
    host: Dict[str, Any]
    data: bytes


def weighted_cross_entropy(logits, labels, sample_weights):
    """torch ``CrossEntropyLoss(weight=w, reduction='mean')`` semantics:
    sum(w_y * ce) / sum(w_y) (strategy.py:352-356); padding rows carry
    weight 0."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ce = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    denom = jnp.maximum(jnp.sum(sample_weights), 1e-12)
    return jnp.sum(ce * sample_weights) / denom


class Trainer:
    """Owns the jitted train/eval steps for one (model, train-config) pair."""

    def __init__(self, model, train_cfg: TrainConfig, mesh,
                 num_classes: int, train_bn: Optional[bool] = None,
                 current_ckpt_every: Optional[int] = None):
        self.model = model
        self.cfg = train_cfg
        self.mesh = mesh
        self.num_classes = num_classes
        if current_ckpt_every is None:
            current_ckpt_every = train_cfg.current_ckpt_every
        self.current_ckpt_every = max(1, int(current_ckpt_every))
        self.logger = get_logger()
        self.tx = make_optimizer(train_cfg.optimizer)
        # The fused update path (train/optim.FusedSGD, DESIGN.md §4):
        # one tree-fused SGD+momentum+wd+apply expression inside the
        # donated step instead of the optax chain's four traversals —
        # bit-identical to optax at f32 state, bf16 momentum optional.
        # None = the optax chain (non-SGD optimizers, fused "off").
        self.fused_tx = make_fused_optimizer(train_cfg)
        # Gradient-sync precision (parallel/mesh.resolve_grad_allreduce):
        # "f32" keeps the partitioner's bit-exact psum inside plain jit;
        # "int8"/"auto" build the shard_map step with the EQuARX-style
        # block-scaled quantized sync (multi-device meshes only), whose
        # WIRE form resolve_int8_wire picks per mesh: the proven
        # all-gather form through 8 devices, the pod-tier
        # reduce-scatter form above the crossover ("int8_rs" forces it
        # — A/B captures and the chaos matrix).
        _ar_mode = getattr(train_cfg, "grad_allreduce", "f32") or "f32"
        self.grad_allreduce = mesh_lib.resolve_grad_allreduce(_ar_mode,
                                                              mesh)
        self.grad_sync_form = (
            mesh_lib.resolve_int8_wire(_ar_mode, mesh)
            if self.grad_allreduce == "int8" else None)
        self.lr_at = make_lr_schedule(train_cfg.scheduler,
                                      train_cfg.optimizer.lr)
        # Reference quirk (strategy.py:366-367): BN runs in eval mode during
        # training whenever features are frozen or a pretrained ckpt is
        # configured.
        if train_bn is None:
            train_bn = not (model.freeze_feature or train_cfg.has_pretrained)
        self.train_bn = train_bn
        self.n_devices = mesh.devices.size
        # Host-side space-to-depth for streamed (host-batched) paths: the
        # s2d model accepts either layout, so resident/epoch-scan gathers
        # stay raw 3-channel and transform on device for free.
        self._host_s2d = getattr(model, "stem", "default") == "s2d"
        self._train_step = (self._build_train_step_int8()
                            if self.grad_allreduce == "int8"
                            else self._build_train_step())
        self._chained_train_step = self._build_chained_train_step()
        self._epoch_scan: Optional[Callable] = None  # built on first use
        # Donated round-boundary optimizer reset (fused path) — lazy.
        self._reinit_opt: Optional[Callable] = None
        # The resident feed's per-batch execution form (CPU meshes; see
        # _build_resident_batch_step) — also lazy.
        self._resident_batch_step: Optional[Callable] = None
        # The row shape both forms were built for (_ensure_exec_form).
        self._exec_row_shape: Optional[Tuple[int, ...]] = None
        # The generalized jit-compile counter (telemetry/runtime.py): a
        # no-op unless a run installed telemetry, so unit-test Trainers
        # never accumulate in a process-global registry.
        rt = tele_runtime.get_run()
        rt.register_jit(f"train_step@{id(self):x}", self._train_step)
        rt.register_jit(f"chained_train_step@{id(self):x}",
                        self._chained_train_step)
        self._eval_steps: Dict[Any, Callable] = {}
        # ONE device-resident pool cache for the whole experiment, shared
        # between evaluation (here) and acquisition scoring (the Strategy
        # passes it into collect_pool): pools keyed by their UNDERLYING
        # images array, so al/train views sharing storage upload once and
        # the resident budget is per-array, not per-consumer.
        self.resident_pool: Dict[Any, Any] = {}
        # Concrete resident-pool byte budget: config None = AUTO-sized
        # from live HBM headroom (parallel/resident.resolve_budget);
        # refresh_resident_budget() re-sizes it at round start.
        from ..parallel import resident as resident_lib
        self.resident_budget = resident_lib.resolve_budget(
            train_cfg.resident_scoring_bytes)
        # True while the degradation ladder's feed_host rung holds the
        # budget at 0: the round-start AUTO refresh must not quietly
        # re-admit the resident path mid-degraded-round (set via
        # set_resident_budget(pin=True); relax() unpins).
        self._budget_pinned = False
        # Resident-pool LAYOUT, resolved ONCE for the experiment
        # (DESIGN.md §2b): "row" shards pool rows over the mesh's data
        # axis (per-chip residency = rows/ndev), "replicated" pins one
        # copy per chip.  _shard_ways feeds the eligibility math: under
        # row sharding a chip pins ceil(rows/ndev) rows, so the budget
        # admits pools ~ndev times larger.
        self.pool_sharding = resident_lib.resolve_sharding(
            getattr(train_cfg, "pool_sharding", "auto"), mesh)
        self._shard_ways = (self.n_devices
                            if self.pool_sharding == "row" else 1)
        # The feed the LAST fit actually used + its host-stall figures —
        # round-boundary telemetry (driver gauges) and bench attribution
        # read it; {"source": None} until a fit has run.
        self.last_feed: Dict[str, Any] = {"source": None}
        # ONE enqueue order for collective-bearing dispatches: the
        # pipelined round's speculative scorer dispatches pool chunks
        # from its own thread while fit/evaluate dispatch train and
        # validation steps here — two threads interleaving collective
        # computations with per-device reordering is how a mesh
        # deadlocks.  Every jitted dispatch below (and collect_pool's,
        # via Strategy/pipeline passing this gate) holds it around the
        # enqueue; on CPU meshes the pipelined round additionally flips
        # the gate's drain_mode so each computation COMPLETES before the
        # gate releases (XLA:CPU does not preserve enqueue order at
        # execution — mesh_lib.DispatchGate).  Sequential paths see an
        # uncontended lock and a no-op drain: nanoseconds.
        self.dispatch_lock = mesh_lib.DispatchGate()

    def refresh_resident_budget(self) -> int:
        """Re-size the AUTO resident budget from current HBM headroom
        (called by the driver at round start).  AUTO-budget pools already
        uploaded stay resident regardless — their bytes are already
        counted in bytes_in_use, so a post-upload refresh must not evict
        them (parallel/resident.cached).  An EXPLICIT budget is enforced
        instead: pools over it demote LRU-first (the clean-shrink path —
        a resumed run with a smaller --resident_scoring_bytes, or an
        in-process set_resident_budget)."""
        from ..parallel import resident as resident_lib
        if self._budget_pinned or self.cfg.resident_scoring_bytes is not None:
            # Pinned (the ladder's feed_host rung) or explicit: enforce
            # the held budget instead of re-auto-sizing — a degraded
            # round attempt must actually run degraded.
            resident_lib.enforce_budget(self.resident_pool,
                                        self.resident_budget)
        else:
            # Pass the cache: pinned pools sit inside bytes_in_use, so
            # the headroom-derived budget must add them back to stay a
            # TOTAL cap under the shared eligible() accounting.
            self.resident_budget = resident_lib.resolve_budget(
                None, cache=self.resident_pool)
        return self.resident_budget

    def set_resident_budget(self, budget: int, pin: bool = False) -> list:
        """Shrink (or grow) the resident budget mid-run: the new budget
        is enforced immediately — pinned pools over it demote LRU-first
        and every consumer (scoring, evaluation, the resident-gather
        train feed, including its auto-mode resident_copy fallback,
        whose private upload is charged against the same budget) falls
        back to its host path at the next call, without a batch-shape
        change or a recompile.  Only an EXPLICIT device_resident=True
        keeps the copy-scan path regardless (the operator forced it).
        Returns the demoted cache keys.  ``pin=True`` (the degradation
        ladder) additionally holds the value across the round-start AUTO
        refresh; the default unpins."""
        from ..parallel import resident as resident_lib
        self.resident_budget = int(budget)
        self._budget_pinned = bool(pin)
        return resident_lib.enforce_budget(self.resident_pool,
                                           self.resident_budget)

    # -- setup -----------------------------------------------------------

    def padded_batch_size(self, batch_size: int) -> int:
        """Round up so the batch axis divides evenly over the mesh; padding
        rows are masked out of every reduction."""
        n = self.n_devices
        return -(-batch_size // n) * n

    def eval_batch_size(self, dataset=None) -> int:
        """Global evaluation batch: the reference's test-loader batch (100)
        on CPU, raised on accelerators — the eval pass is per-example
        counts under eval-mode BN, so batch size is throughput-only (same
        policy as acquisition scoring, TrainConfig.score_batch_size).

        The accelerator floor scales with row size (v5e alt-batch probes,
        BENCH r5): 32px ResNet scoring gains +47% at 512 rows/chip over
        256, ImageNet-res scoring +11% at 256 over 128 — small images
        leave the MXU idle at small batches.  128 when the dataset (and
        so the row shape) is unknown."""
        bs = self.cfg.loader_te.batch_size
        shape = getattr(dataset, "image_shape", None)
        if shape and rows_are_tokens(dataset):
            # A row of T token ids is T positions of work and of
            # activations: the loader's batch is already a step of
            # batch x T tokens, and no floor is put under it.
            return bs
        if self.mesh.devices.flat[0].platform != "cpu":
            floor = 128
            if shape:
                floor = 512 if shape[0] <= 64 else 256
            bs = max(bs, floor * self.n_devices)
        return bs

    def _opt_init(self, params) -> Any:
        return (self.fused_tx.init(params) if self.fused_tx is not None
                else self.tx.init(params))

    def init_state(self, rng: jax.Array, sample_input: np.ndarray
                   ) -> TrainState:
        variables = self.model.init(rng, jnp.asarray(sample_input),
                                    train=False)
        return self.state_of(mesh_lib.replicate(variables, self.mesh))

    def state_of(self, variables, frozen=None) -> TrainState:
        """A TrainState with a fresh optimizer state around variables
        that are already replicated on the mesh (the re-initialisation
        program's own outputs, Strategy.init_network_weights).  The
        frozen leaves come beside them (``frozen``: the strategy's, loaded
        once) or, from a whole ``model.init`` tree, are split off here."""
        params = variables["params"]
        if frozen is None:
            params, frozen = backbone.split_params(
                params, backbone.frozen_prefixes(self.model))
        opt_state = mesh_lib.replicate(self._opt_init(params), self.mesh)
        return TrainState(params=params,
                          batch_stats=variables.get("batch_stats", {}),
                          opt_state=opt_state, step=jnp.zeros((), jnp.int32),
                          frozen=frozen, counters=self._zero_counters())

    def _zero_counters(self) -> Dict[str, Any]:
        names = tuple(getattr(self.model, "row_counters", ()))
        if not names:
            return {}
        return mesh_lib.replicate(
            {n: np.zeros((), np.int32) for n in names}, self.mesh)

    @staticmethod
    def _opt_state_live(opt_state) -> bool:
        """True when every leaf is a live (non-donated) device array —
        the fused reinit may only zero buffers in place if the previous
        round actually left them alive (a crashed attempt's restore
        keeps the donated opt_state of the failed fit)."""
        try:
            return all(not leaf.is_deleted()
                       for leaf in jax.tree.leaves(opt_state)
                       if hasattr(leaf, "is_deleted"))
        except Exception:  # noqa: BLE001 - conservatively reallocate
            return False

    def reinit_optimizer(self, state: TrainState) -> TrainState:
        """Fresh optimizer state at the start of each round (the reference
        constructs a new optimizer per round, strategy.py:345).

        Fused path: the prior round's momentum buffers are DONATED into
        a jitted zeroing — XLA reuses the allocations in place, so the
        round boundary adds no optimizer allocation and no host->device
        upload (the optax path re-built the tree on host and re-uploaded
        it every round; pinned in tests/test_backward.py).  Falls back
        to a fresh init when the buffers are not live (first round, or a
        failed attempt's restore left donated arrays behind)."""
        if self.fused_tx is not None and self._opt_state_live(
                state.opt_state) and jax.tree.leaves(state.opt_state):
            if self._reinit_opt is None:
                # out_shardings pins the REPLICATED layout: without it
                # the zeroed tree comes back single-device, and the
                # next fit's first train step would recompile against
                # the changed input sharding (the zero-recompile
                # warm-round invariant).
                @functools.partial(
                    jax.jit, donate_argnums=(0,),
                    out_shardings=mesh_lib.replicated_sharding(self.mesh))
                def _zero(opt_state):
                    return jax.tree.map(jnp.zeros_like, opt_state)
                self._reinit_opt = _zero
                tele_runtime.get_run().register_jit(
                    f"reinit_opt@{id(self):x}", self._reinit_opt)
            return state.replace(opt_state=self._reinit_opt(state.opt_state),
                                 step=jnp.zeros((), jnp.int32),
                                 counters=self._zero_counters())
        opt_state = mesh_lib.replicate(self._opt_init(state.params),
                                       self.mesh)
        return state.replace(opt_state=opt_state,
                             step=jnp.zeros((), jnp.int32),
                             counters=self._zero_counters())

    def replace_variables(self, state: TrainState, variables) -> TrainState:
        """``variables`` shaped like ``state.trainable_variables`` (a
        checkpoint file, a snapshot): the frozen leaves stay the arrays
        they are.  A tree that still holds frozen keys (a checkpoint of
        before the split) gives them up here."""
        params, _ = backbone.split_params(variables["params"],
                                          tuple(state.frozen))
        variables = mesh_lib.replicate(
            {"params": params,
             "batch_stats": variables.get("batch_stats", {})}, self.mesh)
        return state.replace(params=variables["params"],
                             batch_stats=variables["batch_stats"])

    # -- jitted steps ----------------------------------------------------

    def _apply_optimizer(self, grads, state: TrainState, lr):
        """ONE optimizer-application rule shared by every step builder:
        the fused single-pass update (train/optim.FusedSGD — donated
        momentum, optional bf16 state) when enabled, else the optax
        chain exactly as before.  Bit-identical at f32 state (pinned in
        tests/test_backward.py).  Traced inside the jitted steps."""
        if self.fused_tx is not None:
            return self.fused_tx.update(grads, state.opt_state,
                                        state.params, lr)
        updates, new_opt_state = self.tx.update(grads, state.opt_state,
                                                state.params)
        updates = jax.tree.map(lambda u: -lr * u, updates)
        return optax.apply_updates(state.params, updates), new_opt_state

    def _build_train_step(self):
        model = self.model
        train_bn = self.train_bn
        apply_optimizer = self._apply_optimizer

        forward = self._counted_forward(model, train_bn)

        def loss_fn(params, frozen, batch_stats, x, labels, weights, mask):
            logits, new_stats, counts = forward(params, frozen, batch_stats,
                                                x, mask)
            loss = weighted_cross_entropy(logits, labels, weights)
            return loss, (new_stats, counts)

        def train_step(state, batch, key, lr, class_weights, view):
            # The named scopes (view / forward_backward / optimizer) are
            # metadata on the operations: a device trace attributes the
            # step's time to them, the compiled program is unchanged.
            with jax.named_scope("view"):
                x = apply_view(batch["image"], view, key=key, train=True)
            with jax.named_scope("forward_backward"):
                weights = class_weights[batch["label"]] * batch["mask"]
                # Differentiated in ``state.params`` alone: the gradient
                # tree has no frozen leaf.
                (loss, (new_stats, counts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, state.frozen,
                                           state.batch_stats, x,
                                           batch["label"], weights,
                                           batch["mask"])
            # Telemetry rider: the global gradient norm, computed where
            # the grads already exist (~|params| FLOPs vs the backward
            # pass's billions) and fetched in the SAME deferred bulk
            # materialization as the loss — zero extra device syncs.
            # Params/opt updates are untouched, so path equality
            # (tests/test_trainer_parallel.py) is unaffected.
            with jax.named_scope("optimizer"):
                gnorm = optax.global_norm(grads)
                params, new_opt_state = apply_optimizer(grads, state, lr)
            return state.replace(params=params, batch_stats=new_stats,
                                 opt_state=new_opt_state,
                                 step=state.step + 1,
                                 counters=jax.tree.map(
                                     jnp.add, state.counters, counts)
                                 ), loss, gnorm

        return StateStep(jax.jit(frozen_beside(train_step),
                                 static_argnames=("view",),
                                 donate_argnums=(0,)))

    @staticmethod
    def _counted_forward(model, train_bn: bool):
        """``(params, frozen, batch_stats, x, mask) -> (logits, new
        batch_stats, counts)``: the one forward of every train step.  The
        model's own input stage runs under the ``view`` scope; its
        ``row_counters`` come back summed over the batch's real rows."""
        names = tuple(getattr(model, "row_counters", ()))

        def forward(params, frozen, batch_stats, x, mask):
            variables = {"params": backbone.merge_params(params, frozen),
                         "batch_stats": batch_stats}
            with jax.named_scope("view"):
                x = backbone.input_stage(model, variables, x)
            mutable = (["batch_stats"] if train_bn else []) + (
                ["counters"] if names else [])
            if mutable:
                logits, mutated = model.apply(variables, x, train=train_bn,
                                              mutable=mutable)
            else:
                logits, mutated = model.apply(variables, x, train=False), {}
            new_stats = mutated["batch_stats"] if train_bn else batch_stats
            counts = backbone.sum_counters(mutated.get("counters", {}),
                                           names, mask)
            return logits, new_stats, counts

        return forward

    def _build_train_step_int8(self):
        """The quantized-gradient-sync train step (DESIGN.md §4): the
        same signature and contract as ``_build_train_step`` — every
        wrapper (chained/resident/epoch-scan) composes unchanged — but
        built over ``shard_map`` so the gradient reduction is OURS, not
        the partitioner's: each device computes grads of its batch
        shard's slice of the global loss, then syncs them through the
        EQuARX-style block-scaled int8 sync in whichever WIRE form the
        mesh resolved (mesh_lib.int8_allreduce on 2-8 device meshes;
        mesh_lib.int8_reduce_scatter — the pod-tier form whose wire
        bytes stay ~2n regardless of device count — above the
        crossover, DESIGN.md §15).  BatchNorm keeps GLOBAL-batch
        statistics via explicitly
        pmean'd means (the model is cloned with ``axis_name`` when it
        supports one; BN-free models run as-is).  This path is
        BOUNDED-DELTA vs the f32 step, never bit-exact — it only builds
        when ``--grad_allreduce int8`` survives the resolve rule and
        the driver's learning probe."""
        axis = mesh_lib.DATA_AXIS
        mesh = self.mesh
        ndev = self.n_devices
        sync_form = self.grad_sync_form
        train_bn = self.train_bn
        apply_optimizer = self._apply_optimizer
        try:
            model = self.model.clone(axis_name=axis)
            self._int8_axis_fallback = False
        except TypeError:
            # Models without an axis_name field carry no way to sync
            # cross-device statistics.  Fine for BN-free models (the
            # test classifiers); a train-mode-BN model here would
            # silently compute per-shard statistics — fit() refuses
            # that combination loudly (the batch_stats tree tells it
            # whether mutable statistics actually exist).
            model = self.model
            self._int8_axis_fallback = True
        from jax import shard_map

        forward = self._counted_forward(model, train_bn)

        def loss_fn(params, frozen, batch_stats, x, labels, weights, mask):
            logits, new_stats, counts = forward(params, frozen, batch_stats,
                                                x, mask)
            # The global weighted CE, written shard-locally: local
            # numerator over the GLOBAL (psum'd) denominator — the
            # per-shard losses SUM to the global loss, so summed local
            # grads == global grads (the DDP contract).
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ce = -jnp.take_along_axis(
                logp, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
            denom = jnp.maximum(
                jax.lax.psum(jnp.sum(weights), axis), 1e-12)
            return jnp.sum(ce * weights) / denom, (new_stats, counts)

        def body(state, batch, key, lr, class_weights, view):
            # Decorrelate per-shard augmentation draws: each shard sees
            # a fold_in'd key (the f32 path draws one batch-wide key;
            # int8 is bounded-delta, not bit-exact, by contract).
            aug_key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            with jax.named_scope("view"):
                x = apply_view(batch["image"], view, key=aug_key,
                               train=True)
            with jax.named_scope("forward_backward"):
                weights = class_weights[batch["label"]] * batch["mask"]
                (loss_local, (new_stats, counts)), grads = \
                    jax.value_and_grad(loss_fn, has_aux=True)(
                        state.params, state.frozen, state.batch_stats, x,
                        batch["label"], weights, batch["mask"])
            counts = jax.lax.psum(counts, axis)
            if sync_form == "reduce_scatter":
                grads = mesh_lib.int8_reduce_scatter(grads, ndev, axis)
            else:
                grads = mesh_lib.int8_allreduce(grads, axis)
            loss = jax.lax.psum(loss_local, axis)
            with jax.named_scope("optimizer"):
                gnorm = optax.global_norm(grads)
                params, new_opt_state = apply_optimizer(grads, state, lr)
            return state.replace(params=params, batch_stats=new_stats,
                                 opt_state=new_opt_state,
                                 step=state.step + 1,
                                 counters=jax.tree.map(
                                     jnp.add, state.counters, counts)
                                 ), loss, gnorm

        def train_step(state, batch, key, lr, class_weights, view):
            sharded = shard_map(
                functools.partial(body, view=view), mesh=mesh,
                in_specs=(mesh_lib.P(), mesh_lib.P(axis), mesh_lib.P(),
                          mesh_lib.P(), mesh_lib.P()),
                out_specs=(mesh_lib.P(), mesh_lib.P(), mesh_lib.P()),
                check_vma=False)
            return sharded(state, batch, key, lr, class_weights)

        return StateStep(jax.jit(frozen_beside(train_step),
                                 static_argnames=("view",),
                                 donate_argnums=(0,)))

    def _build_chained_train_step(self):
        """The host-batched fit path's step with the per-batch PRNG split
        folded into the same jitted call — ONE dispatch per batch instead
        of two (an eager ``jax.random.split`` is its own device
        dispatch).  Key
        consumption is identical to ``split`` + ``_train_step``, i.e. the
        exact chain the device-resident epoch scan replicates, so all
        three paths stay bit-identical (tests/test_trainer_parallel.py)."""
        train_step = self._train_step

        def chained(state, batch, key, lr, class_weights, view):
            new_key, sub = jax.random.split(key)
            new_state, loss, gnorm = train_step(state, batch, sub, lr,
                                                class_weights, view=view)
            return new_state, new_key, loss, gnorm

        # (Behind the frozen leaves at 1, the call's key at 2 is at 3.)
        return StateStep(jax.jit(frozen_beside(chained),
                                 static_argnames=("view",),
                                 donate_argnums=(0, 3)))

    def _get_eval_step(self, view):
        if view not in self._eval_steps:
            self._eval_steps[view] = make_eval_step(
                self.model, view, self.num_classes)
        return self._eval_steps[view]

    def _build_resident_batch_step(self, row_shape: Tuple[int, ...]):
        """The resident-gather feed's PER-BATCH execution form: one
        jitted dispatch = on-device gather from the pinned pool + the
        chained PRNG split + the train step.  Key consumption and batch
        bytes are exactly the epoch scan's (and the host path's), so all
        forms produce the same batch stream; this form exists because
        XLA:CPU executes large conv bodies INSIDE ``lax.scan`` several
        times slower than the same ops dispatched directly (measured 6x
        on ResNet-18 at 112px), while on accelerators the scan's
        one-dispatch-per-epoch wins.  Compiles once per experiment AND
        POOL LAYOUT (the pool shape is constant and the index vector is
        [batch]-sized — no step bucketing involved; ``sharded`` is
        static and fixed per experiment, so warm rounds still add zero
        compiles).  With a row-sharded pool the gather goes through
        resident.sharded_pool_gather (owner psum into the batch
        sharding) instead of a full-array index — same bytes, same
        batch sharding, bit-identical training."""
        train_step = self._train_step
        mesh = self.mesh
        from ..parallel import resident as resident_lib

        def resident_batch_step(state, images, labels, ids, mask, key,
                                lr, class_weights, view, sharded=False):
            img, lab = resident_lib.pool_gather(
                images, ids, mesh, row_shape, labels=labels,
                sharded=sharded)
            batch = {"image": img, "label": lab, "mask": mask}
            new_key, sub = jax.random.split(key)
            new_state, loss, gnorm = train_step(state, batch, sub, lr,
                                                class_weights, view=view)
            return new_state, new_key, loss, gnorm

        # (Behind the frozen leaves at 1, the call's key at 5 is at 6.)
        return StateStep(jax.jit(frozen_beside(resident_batch_step),
                                 static_argnames=("view", "sharded"),
                                 donate_argnums=(0, 6)))

    def _build_epoch_scan(self, row_shape: Tuple[int, ...]):
        """One jitted call = one full epoch over device-resident data
        (``images`` in the pinned form of rows of ``row_shape``:
        resident.to_pinned).

        The host-batched path dispatches one jitted step per batch — fine
        when gather/decode is the bottleneck (disk datasets), pure dispatch
        overhead when the whole labeled set already sits in HBM (CIFAR
        scale: 50k x 32x32x3 uint8 = 150 MB).  Here the epoch is a single
        loop over a [steps, batch] index matrix: per step an on-device
        gather + sharding constraint reproduces exactly what
        ``shard_batch`` commits on the host path, and the PRNG-key chain
        (split once per batch) matches it bit for bit, so both paths give
        identical parameters.

        ``steps`` is the bucketed SHAPE (bucket_steps); the loop's trip
        count is a value the program reads off ``valid`` (a prefix of
        ones, _epoch_index_matrix), so only the real steps execute and
        ``losses`` / ``gnorms`` stay zero past them.
        """
        train_step = self._train_step
        mesh = self.mesh
        from ..parallel import resident as resident_lib

        def epoch_scan(state, images, labels, idx_mat, mask_mat, valid,
                       key, lr, class_weights, view, sharded=False):
            # The frozen leaves are read inside the loop, never carried
            # by it: a carried leaf is a buffer the loop owns, and these
            # are not this program's to own.
            frozen = state.frozen

            def body(i, carry):
                state, key, losses, gnorms = carry
                state = state.replace(frozen=frozen)
                new_key, sub = jax.random.split(key)
                # Row-sharded pool: batch rows assembled from their
                # owning shards into the SAME batch sharding the
                # replicated layout's constraint commits — bit-identical
                # batches, shard_map composes inside the loop body.
                img, lab = resident_lib.pool_gather(
                    images, idx_mat[i], mesh, row_shape, labels=labels,
                    sharded=sharded)
                batch = {"image": img, "label": lab, "mask": mask_mat[i]}
                state, loss, gnorm = train_step(state, batch, sub, lr,
                                                class_weights, view=view)
                return (state.replace(frozen={}), new_key,
                        losses.at[i].set(loss), gnorms.at[i].set(gnorm))

            # ``valid`` is replicated: every device reads the same count.
            steps_real = jnp.sum(valid > 0).astype(jnp.int32)
            zeros = jnp.zeros(valid.shape, jnp.float32)
            out = jax.lax.fori_loop(
                0, steps_real, body,
                (state.replace(frozen={}), key, zeros, zeros))
            return (out[0].replace(frozen=frozen),) + tuple(out[1:])

        return StateStep(jax.jit(frozen_beside(epoch_scan),
                                 static_argnames=("view", "sharded"),
                                 donate_argnums=(0,)))

    # Steps (and uploaded rows) are bucketed so the epoch scan compiles
    # once per BUCKET, not once per AL round as the labeled set grows:
    # up to STEP_BUCKET steps everything lands on the one floor bucket,
    # beyond it steps round up to a bounded-waste geometric bucket
    # (pool.bucket_size, 1/8-octave granularity).  The bucket is the
    # SHAPE of the index matrix and of the uploaded rows only: the
    # program runs ``sum(valid)`` steps, so padding costs upload bytes
    # (25% worst-case, typically a few %), never a train step.  Bucket
    # size never changes numerics.
    STEP_BUCKET = 16

    @classmethod
    def bucket_steps(cls, steps_real: int) -> int:
        from ..pool import bucket_size
        return bucket_size(steps_real, floor=cls.STEP_BUCKET)

    # -- the train-feed hierarchy ----------------------------------------

    def resolve_train_feed(self, train_set: Dataset,
                           labeled_idxs: np.ndarray,
                           batch_hook=None) -> str:
        """Pick one feed for a whole fit (resolved ONCE, at fit start —
        a feed must never change mid-fit or a warm round would recompile):

          "resident"      on-device gather of labeled indices from the
                          SAME pinned pool that serves scoring and
                          evaluation — zero host image copies, augment
                          on device inside the epoch scan;
          "resident_copy" the legacy labeled-subset upload + epoch scan
                          (now the special case of resident-gather for
                          pools whose full array doesn't fit the budget
                          while the labeled slice does);
          "host_prefetch" multi-worker gather/decode behind the
                          double-buffered device prefetch
                          (data/pipeline.train_feed_batches);
          "host_serial"   the plain per-batch gather->shard->step loop
                          (always the path under a VAAL batch_hook,
                          which consumes host-ordered sharded batches).

        Every feed yields a bit-identical batch stream at the same rng /
        PRNG-key state (tests/test_trainer_parallel.py) — this decision
        is throughput-only.  cfg.train_feed forces a leg ("resident" /
        "host"); "auto" walks the hierarchy top-down.  cfg.device_resident
        keeps its meaning as the epoch-scan gate: False pins the host
        leg, None applies the measured auto rule (always on accelerators,
        >= 2048 labeled rows on CPU — the scan's extra compile must
        amortize)."""
        from ..parallel import resident as resident_lib
        mode = getattr(self.cfg, "train_feed", "auto") or "auto"
        if mode not in ("auto", "resident", "host"):
            # Fail fast on the first fit: argparse guards the CLI, but a
            # programmatic config with a typo'd mode must not silently
            # train on a different feed than the caller believes.
            raise ValueError(
                f"train_feed={mode!r} is not one of 'auto'/'resident'/"
                "'host'")
        images = getattr(train_set, "images", None)
        in_mem = isinstance(images, np.ndarray)
        # The disk tier (data/diskpool.py, DESIGN.md §16): a paged pool
        # exposes no whole-pool array (``.images`` raises — the static
        # no-materialization contract), but its ``gather`` pages the
        # LABELED rows in bucket-aligned blocks, so the hot tier — the
        # private labeled-subset HBM copy — still applies.  Excluded on
        # multi-process meshes: the copy gathers GLOBAL labeled rows,
        # and each host's disk tier holds only its own row range.
        paged = bool(getattr(train_set, "paged_backend", False)) \
            and not mesh_lib.is_multiprocess(self.mesh)
        hook_free = batch_hook is None

        prefetched = hook_free and (self._feed_workers() > 0
                                    or self.cfg.loader_tr.prefetch > 0)
        host = "host_prefetch" if prefetched else "host_serial"

        scan_possible = hook_free and (in_mem or paged) \
            and self.cfg.device_resident is not False
        resident_ok = scan_possible and resident_lib.eligible(
            train_set, self.resident_budget, cache=self.resident_pool,
            shard_ways=self._shard_ways)
        if mode == "resident":
            if resident_ok:
                return "resident"
            self.logger.warning(
                "train_feed=resident requested but the pool cannot pin "
                "(disk-backed, batch_hook, device_resident=False, or "
                "over the resident budget); falling back down the feed "
                "hierarchy")
            mode = "auto"
        if mode == "host":
            return host
        # auto: the epoch scan must be worthwhile before any resident leg
        # engages (on CPU a small fit's scan compile costs more than it
        # saves; on accelerators per-batch h2d + dispatch always loses).
        on_accel = self.mesh.devices.flat[0].platform != "cpu"
        scan_worthwhile = scan_possible and (
            self.cfg.device_resident is True
            or (self.cfg.device_resident is None
                and (on_accel or len(labeled_idxs) >= 2048)))
        if scan_worthwhile:
            if resident_ok:
                return "resident"
            bs = self.padded_batch_size(self.cfg.loader_tr.batch_size)
            # Backend-agnostic row bytes: a paged pool has no whole
            # array to read shape/itemsize off (uint8 rows by the disk
            # tier's storage contract).
            row_bytes = (int(np.prod(images.shape[1:])) * images.itemsize
                         if in_mem
                         else int(np.prod(train_set.image_shape)))
            copy_bytes = (self.bucket_steps(num_batches(len(labeled_idxs),
                                                        bs)) * bs
                          * row_bytes)
            # The legacy whole-array size guard applies to what actually
            # materializes: the full pool on the in-memory backend, only
            # the hot labeled copy on the paged one (the pool itself is
            # deliberately bigger than any host's RAM there).
            size_guard = (images.nbytes if in_mem else copy_bytes)
            if size_guard <= 2 ** 31 and (
                    # Explicit device_resident=True keeps its legacy
                    # meaning (force the scan path regardless of the
                    # residency budget); under AUTO the private labeled
                    # copy is HBM like any pinned array and must fit the
                    # shared budget — after a mid-run demote, "fall back
                    # to the host path" must mean the host path, not an
                    # unaccounted re-upload.
                    self.cfg.device_resident is True
                    or resident_lib.pinned_bytes(self.resident_pool)
                    + copy_bytes <= self.resident_budget):
                return "resident_copy"
        return host

    def _ensure_exec_form(self, feed: str,
                          row_shape: Tuple[int, ...]) -> bool:
        """ONE rule for which jitted execution form a resident-feed fit
        uses — shared by fit and the select-time prefetch
        (prepare_next_fit), so the prefetch can never warm a form the
        fit won't pick.  Lazily builds + registers the chosen form and
        returns ``use_scan``: one scan dispatch per epoch on
        accelerators (and when the scan is explicitly forced), one
        jitted gather+step dispatch per batch on CPU meshes — XLA:CPU
        runs conv bodies inside lax.scan several times slower than
        directly-dispatched ops (_build_resident_batch_step), and the
        per-batch form also skips the step-bucket padding entirely.
        Both forms read rows of ``row_shape`` out of a pinned array
        (resident.pool_gather); a fit over rows of another shape gets
        programs of its own."""
        row_shape = tuple(int(d) for d in row_shape)
        if row_shape != self._exec_row_shape:
            self._epoch_scan = self._resident_batch_step = None
            self._exec_row_shape = row_shape
        scan_form = (self.mesh.devices.flat[0].platform != "cpu"
                     or self.cfg.device_resident is True)
        use_scan = (feed == "resident_copy"
                    or (feed == "resident" and scan_form))
        if use_scan and self._epoch_scan is None:
            self._epoch_scan = self._build_epoch_scan(row_shape)
            tele_runtime.get_run().register_jit(
                f"epoch_scan@{id(self):x}", self._epoch_scan)
        if (feed == "resident" and not use_scan
                and self._resident_batch_step is None):
            self._resident_batch_step = self._build_resident_batch_step(
                row_shape)
            tele_runtime.get_run().register_jit(
                f"resident_batch_step@{id(self):x}",
                self._resident_batch_step)
        return use_scan

    def prepare_next_fit(self, train_set: Dataset, labeled_now: np.ndarray,
                         expected_labeled: int) -> Optional[str]:
        """Select-time train prefetch (the pipelined round, DESIGN.md
        §8): while k-center/BADGE selection runs its collective scans on
        the main thread, pre-resolve the feed the COMING fit will take
        — sized at the post-selection labeled count, which is known
        before the picks are — and warm what it touches, so ``fit``
        starts with zero feed stall at step 0:

          * resident-gather: ensure the shared pool is pinned (an upload
            here is one the fit no longer pays) and pre-build the jitted
            execution form the fit will pick, so its first step is a
            cache lookup;
          * host feeds: warm the gather/decode path (memmap cache, page
            cache) over the rows ALREADY labeled — the new picks don't
            exist until selection returns, but they are ``round_budget``
            of ``expected_labeled`` rows; the rest re-decode warm.

        rng-free and state-free by contract: everything here is work the
        fit would do anyway, done early — pipelined and sequential
        rounds stay bit-identical.  Returns the resolved feed (None on
        failure; prefetch is best-effort)."""
        expected = np.arange(max(0, int(expected_labeled)), dtype=np.int64)
        feed = self.resolve_train_feed(train_set, expected, None)
        if feed == "resident":
            self._resident_feed_arrays(train_set)
        if feed in ("resident", "resident_copy"):
            # The SAME form rule + lazy build the fit runs — shared so
            # the prefetch can never warm a form the fit won't use.
            self._ensure_exec_form(feed, train_set.image_shape)
        elif len(labeled_now):
            # Bounded warm-up of the host gather/decode path; the rows
            # land in the memmap/page cache and are dropped here.
            cap = min(len(labeled_now), 4096)
            train_set.gather(np.asarray(labeled_now[:cap], dtype=np.int64))
        return feed

    def _feed_workers(self) -> int:
        """Gather/decode worker threads for the host train feed:
        TrainConfig.feed_workers, deferring to the train loader's
        num_workers (the reference DataLoader row) when unset.  ONE
        resolution shared by the feed decision and the feed itself."""
        if self.cfg.feed_workers is not None:
            return int(self.cfg.feed_workers)
        return int(self.cfg.loader_tr.num_workers)

    def _resident_feed_arrays(self, train_set: Dataset):
        """The resident-gather feed's arrays: the SAME pinned (pool,
        labels) pair scoring and evaluation use — one upload for the
        whole experiment, no second HBM copy, and NOTHING host-side
        beyond the shared-cache lookup.  Uploaded in the experiment's
        resolved pool layout (row-sharded = rows/ndev per chip).  The
        zero-host-copy invariant is enforced statically:
        scripts/trace_lint.py forbids any np.* or .gather()
        materialization inside this function."""
        from ..parallel import resident as resident_lib
        return resident_lib.pool_arrays(self.resident_pool, train_set,
                                        self.mesh,
                                        sharding=self.pool_sharding)

    def _device_resident_arrays(self, train_set: Dataset,
                                labeled_idxs: np.ndarray, batch_size: int):
        """Upload the labeled subset once, padded up to the row bucket so
        consecutive rounds reuse the same compiled scan (replicated; the
        per-step gather output is what gets data-sharded).  The rows go
        up in the pinned form, like the shared pool's: one gather serves
        both feeds."""
        from ..parallel import resident as resident_lib
        images = train_set.gather(labeled_idxs)
        labels = train_set.targets[labeled_idxs].astype(np.int32)
        padded = self.bucket_steps(
            num_batches(len(labeled_idxs), batch_size)) * batch_size
        pad = padded - len(labeled_idxs)
        if pad:
            images = np.concatenate(
                [images, np.zeros((pad, *images.shape[1:]), images.dtype)])
            labels = np.concatenate([labels, np.zeros(pad, labels.dtype)])
        return (mesh_lib.replicate(
                    jnp.asarray(resident_lib.to_pinned(images)), self.mesh),
                mesh_lib.replicate(jnp.asarray(labels), self.mesh))

    @classmethod
    def _epoch_index_matrix(cls, n: int, batch_size: int,
                            rng: np.random.Generator):
        """Shuffled fixed-shape [steps, batch] LOCAL index matrix, padding
        mask, and per-step validity — consuming the rng exactly like the
        host path's batch_index_lists(shuffle=True)."""
        perm = rng.permutation(np.arange(n))
        steps_real = num_batches(n, batch_size)
        pad = steps_real * batch_size - n
        if pad:
            # Pad with the last batch's first row — the exact rows
            # gather_batch pads with, so BN batch statistics match the
            # host-batched path bit for bit.
            perm = np.concatenate(
                [perm, np.repeat(perm[(steps_real - 1) * batch_size], pad)])
        mask = np.ones(steps_real * batch_size, dtype=np.float32)
        if pad:
            mask[n:] = 0.0
        steps = cls.bucket_steps(steps_real)
        idx_mat = np.zeros((steps, batch_size), dtype=np.int32)
        mask_mat = np.zeros((steps, batch_size), dtype=np.float32)
        idx_mat[:steps_real] = perm.reshape(steps_real, batch_size)
        mask_mat[:steps_real] = mask.reshape(steps_real, batch_size)
        valid = np.zeros(steps, dtype=np.float32)
        valid[:steps_real] = 1.0
        return idx_mat, mask_mat, valid, steps_real

    # -- per-epoch telemetry ----------------------------------------------

    # EMA smoothing for the loss/grad-norm telemetry series (per-epoch
    # cadence; ~10-epoch effective window).
    TELEMETRY_EMA_ALPHA = 0.2

    @staticmethod
    def _emit_epoch_telemetry(metric_cb, round_idx: int, epoch: int,
                              n_epoch: int, n_images: int,
                              dispatch_wall: float, synced_wall: float,
                              synced: bool, steps: int,
                              step_times: List[float]) -> None:
        """Step-time p50/p99 and imgs/sec for one epoch, through the
        caller's metric sink — with nothing dishonest on async backends
        (jax dispatch returns before the device finishes, and this path
        deliberately adds NO device sync of its own):

          * host-batched path (``step_times`` non-empty): loop-cadence
            percentiles — each delta spans gather + dispatch, and the
            donated-buffer backpressure makes steady-state cadence track
            real step time;
          * epoch-scan path (ONE dispatch per epoch): the only honest
            anchor is the validation fetch that follows the scan, so the
            per-step mean is derived from the SYNCED train+val wall
            (p50 == p99 labels it as a mean; slightly over-counting val
            beats under-counting the scan by orders of magnitude);
          * epoch-scan without early stopping: no sync exists anywhere
            in the epoch — nothing trustworthy to emit, so nothing is.

        Step axis: the same round-folded epoch counter set_epoch uses,
        so multi-round runs keep a monotonic x-axis."""
        if metric_cb is None or steps <= 0:
            return
        from ..telemetry.runtime import percentile
        if step_times:
            p50 = percentile(step_times, 0.50)
            p99 = percentile(step_times, 0.99)
            wall = dispatch_wall
        elif synced:
            wall = synced_wall
            p50 = p99 = wall / steps
        else:
            return
        if wall <= 0:
            return
        tele_step = round_idx * (n_epoch + 1) + epoch
        metric_cb("step_time_ms_p50", round(p50 * 1000.0, 3), tele_step)
        metric_cb("step_time_ms_p99", round(p99 * 1000.0, 3), tele_step)
        metric_cb("imgs_per_sec", round(n_images / wall, 1), tele_step)

    def _emit_feed_telemetry(self, metric_cb, tele_step: int,
                             host_waits: List[float],
                             train_wall: float) -> None:
        """Per-epoch feed-boundedness: ``feed_stall_frac`` (fraction of
        the epoch's train wall spent blocked on the host feed) and
        ``host_wait_ms_p50`` (per-batch wait median) — a host-bound
        epoch reads off ``status``/the sink without a profiler.  The
        resident/epoch-scan legs have NO host feed and emit explicit
        zeros: "the feed costs nothing" is a statement, not an absence.
        Both also land in ``last_feed`` for the driver's round gauges
        (Prometheus) and bench attribution."""
        from ..telemetry.runtime import percentile
        if host_waits and train_wall > 0:
            stall = min(1.0, sum(host_waits) / train_wall)
            p50_ms = percentile(host_waits, 0.50) * 1000.0
        else:
            stall, p50_ms = 0.0, 0.0
        self.last_feed["feed_stall_frac"] = round(stall, 4)
        self.last_feed["host_wait_ms_p50"] = round(p50_ms, 3)
        if metric_cb is not None:
            metric_cb("feed_stall_frac", round(stall, 4), tele_step)
            metric_cb("host_wait_ms_p50", round(p50_ms, 3), tele_step)

    # -- class weights ---------------------------------------------------

    def class_weights(self, labels: np.ndarray) -> np.ndarray:
        """Imbalanced-training class weights (strategy.py:444-457):
        observed classes get total/count, unobserved keep 1, normalized to
        sum 1.  Identity (all ones) when imbalanced_training is off."""
        if not self.cfg.imbalanced_training:
            return np.ones(self.num_classes, dtype=np.float32)
        uniq, counts = np.unique(labels, return_counts=True)
        weights = np.ones(self.num_classes, dtype=np.float64)
        weights[uniq] = counts.sum() / counts
        weights /= weights.sum()
        return weights.astype(np.float32)

    # -- evaluation ------------------------------------------------------

    def evaluate(self, state: TrainState, dataset: Dataset,
                 idxs: np.ndarray) -> Dict[str, np.ndarray]:
        """Top-1/top-5/per-class metrics over ``dataset[idxs]``
        (replaces evaluation.py:11-105)."""
        eval_step = self._get_eval_step(dataset.view)
        bs = self.padded_batch_size(self.eval_batch_size(dataset))
        variables = state.variables

        from ..parallel import resident as resident_lib
        if resident_lib.eligible(dataset, self.resident_budget,
                                 cache=self.resident_pool,
                                 shard_ways=self._shard_ways):
            # Device-resident path: on-device row gather per batch, count
            # totals accumulated ON DEVICE (one host fetch at the end) so
            # async dispatch pipelines the whole eval pass; see
            # parallel/resident.py for the shared cache and the
            # virtual-CPU-mesh caveat.  resident_scoring_bytes=0 disables.
            # The runner follows the ENTRY's actual layout (an entry
            # uploaded row-sharded stays row-sharded for every consumer).
            images_dev, labels_dev = resident_lib.pool_arrays(
                self.resident_pool, dataset, self.mesh,
                sharding=self.pool_sharding)
            run = resident_lib.get_runner(
                self.resident_pool, eval_step, self.mesh, "run_eval",
                dataset.image_shape, with_labels=True,
                sharded=mesh_lib.is_row_sharded(images_dev))
            totals = None
            for b in batch_index_lists(np.asarray(idxs), bs):
                ids, mask = padded_batch_layout(b, bs)
                with self.dispatch_lock:
                    small = mesh_lib.replicate((ids.astype(np.int32), mask),
                                               self.mesh)
                    counts = run(variables, images_dev, labels_dev, *small)
                    totals = (counts if totals is None
                              else jax.tree.map(jnp.add, totals, counts))
                    self.dispatch_lock.drain(totals)
            return accumulate_metrics(iter(() if totals is None
                                           else (totals,)))

        local = mesh_lib.process_local_rows(self.mesh, bs)

        def counts():
            for batch in iterate_batches(
                    dataset, idxs, bs,
                    num_threads=self.cfg.loader_te.num_workers,
                    prefetch=self.cfg.loader_te.prefetch, local=local,
                    s2d=self._host_s2d):
                # Dispatch under the lock, yield outside it: the lock
                # orders enqueues only and must never be held across the
                # consumer's (possibly fetching) work.
                with self.dispatch_lock:
                    out = eval_step(variables,
                                    mesh_lib.shard_batch(batch, self.mesh))
                    self.dispatch_lock.drain(out)
                yield out

        return accumulate_metrics(counts())

    # -- the fit loop ----------------------------------------------------

    def _publish_best(self, weight_paths: Dict[str, str], variables,
                      round_idx: int, epoch: int) -> _Published:
        """``ckpt/publish_best``: the device->host fetch (ONE a best:
        every leaf's transfer is started before the first is waited for)
        AND the atomic write + monotonic (round, epoch) tag, as one span
        that ends at the rename.  Returns what it fetched and wrote, for
        the consumers that need the same bytes."""
        with tele_spans.get_tracer().span(
                "ckpt/publish_best",
                args={"bytes": ckpt_lib.tree_bytes(variables)}):
            host = jax.device_get(variables)
            data = ckpt_lib.serialize(host)
            _CKPT_RETRY.call(ckpt_lib.publish_best_bytes,
                             weight_paths["best_ckpt"], data,
                             round_idx=round_idx, epoch=epoch)
        return _Published(epoch, host, data)

    def _save_current(self, weight_paths: Dict[str, str], variables,
                      data: Optional[bytes] = None) -> None:
        """``ckpt/save_current``: fetch + write, ends at the rename.
        ``data``: the serialisation ``ckpt/publish_best`` made of the
        same state (the best IS the current one); ``shared`` says it was
        written in place of a fetch and a serialisation of its own."""
        with tele_spans.get_tracer().span(
                "ckpt/save_current",
                args={"bytes": ckpt_lib.tree_bytes(variables),
                      "shared": data is not None}):
            if data is None:
                data = ckpt_lib.serialize(variables)
            _CKPT_RETRY.call(ckpt_lib.write_bytes,
                             weight_paths["current_ckpt"], data)

    def fit(
        self,
        state: TrainState,
        train_set: Dataset,
        labeled_idxs: np.ndarray,
        al_set: Dataset,
        eval_idxs: np.ndarray,
        n_epoch: int,
        es_patience: int,
        rng: np.random.Generator,
        round_idx: int = 0,
        weight_paths: Optional[Dict[str, str]] = None,
        metric_cb: Optional[Callable[[str, float, int], None]] = None,
        batch_hook: Optional[Callable[[int, Dict[str, np.ndarray]], None]]
        = None,
        resume_fit_state: bool = True,
        on_best: Optional[Callable[[int, int, Dict[str, Any]], None]]
        = None,
    ) -> FitResult:
        """Train on the labeled subset with per-epoch validation + early
        stopping (parallel_train_fn, strategy.py:304-381).

        ``es_patience == 0`` disables early stopping (parser.py:66-69); in
        that case the final parameters become the "best" (the reference
        would crash in load_best_ckpt — deliberate fix).

        ``batch_hook(epoch, host_batch)`` runs after each classifier step —
        the seam that lets VAAL co-train its VAE/discriminator inside the
        same epoch loop (the reference overrides the whole
        parallel_train_fn, vaal_sampler.py:77-183).

        ``on_best(round_idx, epoch, variables)`` fires whenever a new
        best-validation snapshot is taken — the in-process publish leg
        of the best-ckpt bus (the pipelined round's speculative scorer
        subscribes; experiment/pipeline.py).  The variables tree is the
        fresh device-side copy, never donated afterwards, so the
        subscriber may keep using it.  A failing callback is logged and
        ignored: speculation must never take a fit down."""
        use_es = es_patience != 0 and len(eval_idxs) > 0
        tracer = tele_spans.get_tracer()
        with tracer.span("fit/prepare"):
            from ..data.cache import CachedEvalRows, DecodedPoolCache
            if (use_es and self.cfg.cache_eval and hasattr(al_set, "paths")
                    and not al_set.train_transform
                    and not isinstance(al_set, DecodedPoolCache)):
                # Disk-backed eval rows decode identically every epoch (the
                # val view is deterministic) — decode each once per round.
                # Skipped when the experiment-lifetime memmap cache already
                # wraps the pool: rows then stream from the page cache and a
                # second RAM copy buys nothing.
                al_set = CachedEvalRows(al_set,
                                        max_bytes=self.cfg.cache_eval_bytes)
            labels = train_set.targets[labeled_idxs]
            class_weights = jnp.asarray(self.class_weights(labels))
            if (self.grad_allreduce == "int8"
                    and getattr(self, "_int8_axis_fallback", False)
                    and self.train_bn
                    and jax.tree.leaves(state.batch_stats)):
                # The int8 step could not thread the mesh axis into this
                # model (no axis_name field) AND the model carries mutable
                # batch statistics that would train PER-SHARD inside the
                # shard_map body — divergent, silently-wrong BN.  Refuse
                # loudly; grad_allreduce=f32 (or an axis_name-capable
                # model) is the fix.
                raise ValueError(
                    "grad_allreduce=int8 with a train-mode-BatchNorm model "
                    "that has no axis_name field: cross-device statistics "
                    "cannot be synced inside the quantized step — use "
                    "--grad_allreduce f32 or a model exposing axis_name")
            state = self.reinit_optimizer(state)
            bs = self.padded_batch_size(self.cfg.loader_tr.batch_size)

            # The train feed, resolved ONCE for the whole fit (DESIGN.md §2a:
            # resident-gather > prefetched-host > serial-host).  On the
            # resident legs each epoch is ONE jitted scan whose per-step
            # on-device gather + augment reproduce the host stream bit for
            # bit (tests/test_trainer_parallel.py); "resident" draws from the
            # SAME pinned pool scoring/evaluation use (zero host image
            # copies), "resident_copy" from a private labeled-subset upload.
            feed = self.resolve_train_feed(train_set, labeled_idxs, batch_hook)
            use_scan = self._ensure_exec_form(feed, train_set.image_shape)
            self.last_feed = {"source": feed, "feed_stall_frac": None,
                              "host_wait_ms_p50": None,
                              "form": ("scan" if use_scan else
                                       "step" if feed == "resident" else
                                       "loop")}
            feed_map = None
            dr_sharded = False
            if feed == "resident":
                # Local epoch-matrix positions -> GLOBAL pool rows.  int32:
                # resident pools are bounded by HBM, far under 2^31 rows.
                feed_map = np.asarray(labeled_idxs, dtype=np.int32)
                dr_images, dr_labels = self._resident_feed_arrays(train_set)
                # Execution follows the entry's ACTUAL layout (a pool pinned
                # replicated before a config change stays replicated): the
                # flag is static on the jitted forms, fixed per experiment.
                dr_sharded = mesh_lib.is_row_sharded(dr_images)
            elif feed == "resident_copy":
                # The legacy private labeled-subset copy stays replicated
                # (it is bucket-padded per round; sharding it would buy
                # little and cost a layout axis on the step bucketing).
                dr_images, dr_labels = self._device_resident_arrays(
                    train_set, labeled_idxs, bs)
                if getattr(train_set, "paged_backend", False):
                    # The disk tier's HBM leg: the hot copy joins the shared
                    # budget accounting (pinned_bytes/enforce_budget) under
                    # one per-trainer slot — re-pinned each fit, so the
                    # previous round's copy is replaced, never accumulated.
                    from ..parallel import resident as resident_lib
                    resident_lib.pin_hot(self.resident_pool,
                                         f"hot_rows@{id(self):x}",
                                         dr_images, dr_labels)
            best_perf, best_epoch, es_count = 0.0, 0, 0
            best_variables = None  # device tree after an improvement this fit
            best_dirty = False  # True = best_variables newer than best_ckpt
            best_resumed = False  # True = best_variables read from best_ckpt
            published = None  # the newest ckpt/publish_best of this fit
            history: List[Dict[str, float]] = []
            key = jax.random.PRNGKey(int(rng.integers(0, 2 ** 31 - 1)))

            # Mid-round resume: if a fit-state checkpoint for THIS round exists
            # (written periodically below, deleted when the round completes), a
            # crashed/preempted fit continues from its last completed epoch
            # bit-for-bit instead of restarting the round — epoch-granularity
            # recovery the reference lacks (its rd_{n}.pth is written every
            # epoch and never read back, strategy.py:440).  VAAL's co-trained
            # VAE/discriminator state is not covered: with a batch_hook the
            # resumed fit restarts from epoch 1.
            start_epoch = 1
            if weight_paths and batch_hook is None and not resume_fit_state:
                # This fit starts from scratch by the caller's decision (a
                # fresh, non-resumed experiment run).  A fit state on disk here
                # is from an OLDER dead run of the same experiment
                # directory — consuming it would silently splice two runs
                # together.
                if os.path.exists(weight_paths["fit_state"] + ".json"):
                    self.logger.warning(
                        "Discarding a stale mid-round fit state from a "
                        "previous run (start this run with --resume_training "
                        "to consume it)")
                ckpt_lib.delete_fit_state(weight_paths["fit_state"])
            if weight_paths and batch_hook is None and resume_fit_state:
                saved = ckpt_lib.load_fit_state(weight_paths["fit_state"],
                                                round_idx)
                if saved is not None:
                    try:
                        opt_state = serialization.from_state_dict(
                            jax.tree.map(np.asarray, state.opt_state),
                            saved["opt_state"])
                    except Exception:  # noqa: BLE001 - layout drift
                        # The saved optimizer state has a different pytree
                        # layout than this Trainer's (the fused path's
                        # {"trace": ...} vs the optax chain's tuple state —
                        # a --fused_optimizer change, or a pre-fused-era
                        # checkpoint resumed under the new default).  The
                        # fit state is all-or-nothing (its rng chain and
                        # epoch counter assume the whole restore): discard
                        # it and restart the round from scratch rather than
                        # crash the resume.
                        self.logger.warning(
                            "mid-round fit state holds an incompatible "
                            "optimizer-state layout (the optimizer path "
                            "changed between runs); discarding it — round "
                            f"{round_idx} restarts from its first epoch")
                        ckpt_lib.delete_fit_state(weight_paths["fit_state"])
                        saved = None
                if saved is not None:
                    host = jax.tree.map(np.asarray,
                                        state.trainable_variables)
                    variables = serialization.from_state_dict(
                        host, saved["variables"])
                    state = state.replace(
                        params=mesh_lib.replicate(variables["params"],
                                                  self.mesh),
                        batch_stats=mesh_lib.replicate(
                            variables.get("batch_stats", {}), self.mesh),
                        opt_state=mesh_lib.replicate(opt_state, self.mesh),
                        step=jnp.asarray(saved["step"], jnp.int32))
                    best_perf = float(saved["best_perf"])
                    best_epoch = int(saved["best_epoch"])
                    es_count = int(saved["es_count"])
                    key = jnp.asarray(
                        np.asarray(saved["key"], dtype=np.uint32))
                    rng.bit_generator.state = saved["rng_state"]
                    start_epoch = int(saved["epoch"]) + 1
                    if best_epoch > 0:
                        # The COORDINATOR's view of best_ckpt decides for every
                        # process: this branch resets early-stopping control
                        # state (es_count), and a per-process filesystem check
                        # (NFS attribute-cache lag on a pod) could send
                        # processes down different epoch counts — mismatched
                        # collectives hang the job.
                        have_best = os.path.exists(weight_paths["best_ckpt"])
                        if mesh_lib.is_multiprocess(self.mesh):
                            from jax.experimental import multihost_utils
                            have_best = bool(
                                multihost_utils.broadcast_one_to_all(
                                    np.uint8(have_best)))
                        if have_best:
                            best_variables = ckpt_lib.load_variables(
                                weight_paths["best_ckpt"], like=host)
                            best_resumed = True
                        else:
                            # The weights best_perf refers to are gone; keeping
                            # the stale score would make the no-improvement
                            # fallback report it over final-epoch weights.
                            self.logger.warning(
                                "fit-state references best epoch "
                                f"{best_epoch} but best_ckpt is missing; "
                                "restarting best-model tracking")
                            best_perf, best_epoch, es_count = 0.0, 0, 0
                    self.logger.info(
                        f"Resuming round {round_idx} training from epoch "
                        f"{start_epoch} (mid-round fit state)")

            # Per-step/per-epoch telemetry (DESIGN.md §7).  ``collect`` False
            # (no run installed, or telemetry off) must add NO per-step work:
            # every perf_counter call and list append below is gated on it.
            rt = tele_runtime.get_run()
            collect = rt.train_metrics
            n_real = len(labeled_idxs)
            epoch_counts: List[Tuple[Any, Dict[str, Any]]] = []

        epochs_run = 0
        for epoch in range(start_epoch, n_epoch + 1):
            epochs_run = epoch
            # The epoch span times the DISPATCH (on the scan path one
            # asynchronous call: it ends at the enqueue, and the device
            # time is waited for in fit/validate below or, without
            # validation, in the next fetch).  Recorded whenever the
            # recorder is on.
            with tracer.span("epoch", args={
                    "round": round_idx, "epoch": epoch,
                    "rows": n_real}) as epoch_sp:
                step_times: List[float] = []
                if hasattr(train_set, "set_epoch"):
                    # Advance disk datasets' per-(seed, epoch, index) crop RNG
                    # (data/imagenet.py); fold the round in so AL rounds don't
                    # replay the same augmentation sequence.
                    train_set.set_epoch(round_idx * (n_epoch + 1) + epoch)
                lr = jnp.float32(self.lr_at(epoch - 1))
                # train_loss stays a DEVICE scalar until the end of the fit:
                # fetching it here would block the host on the epoch's compute
                # before validation could even be dispatched — one avoidable
                # host sync per epoch (its cost on the v5e: not measured).  The
                # history is materialized to floats right before returning;
                # mid-fit history entries hold live device arrays, so history
                # must never be added to the fit-state payload as-is.
                host_waits: List[float] = []
                if use_scan:
                    idx_mat, mask_mat, valid, steps_real = \
                        self._epoch_index_matrix(len(labeled_idxs), bs, rng)
                    if feed_map is not None:
                        # Resident-gather: the SAME shuffled layout the host
                        # path commits, re-expressed as global pool rows —
                        # index math only, never an image byte.
                        idx_mat = feed_map[idx_mat]
                    with self.dispatch_lock:
                        state, key, losses, gnorms = self._epoch_scan(
                            state, dr_images, dr_labels, jnp.asarray(idx_mat),
                            jnp.asarray(mask_mat), jnp.asarray(valid), key, lr,
                            class_weights, view=train_set.view,
                            sharded=dr_sharded)
                        self.dispatch_lock.drain(losses)
                    epoch_loss = jnp.sum(losses) / steps_real
                    epoch_gnorm = jnp.sum(gnorms) / steps_real
                    steps_run = steps_real
                elif feed == "resident":
                    # Per-batch execution form: the SAME shuffled global
                    # layout (batch_index_lists consumes the rng exactly
                    # like the scan's _epoch_index_matrix and the host
                    # path), each batch one jitted on-device gather + step —
                    # the only h2d per step is the [batch] index vector.
                    losses, gnorms = [], []
                    t_step = time.perf_counter() if collect else 0.0
                    for b in batch_index_lists(labeled_idxs, bs,
                                               shuffle=True, rng=rng):
                        ids, mask = padded_batch_layout(b, bs)
                        with self.dispatch_lock:
                            small = mesh_lib.replicate(
                                (ids.astype(np.int32), mask), self.mesh)
                            state, key, loss, gnorm = \
                                self._resident_batch_step(  # al-lint: donated-ok positions 3-4 are the *small (ids, mask) splat; the donated key at 5 is rebound by this statement's own targets
                                    state, dr_images, dr_labels, *small, key,
                                    lr, class_weights, view=train_set.view,
                                    sharded=dr_sharded)
                            self.dispatch_lock.drain(loss)
                        losses.append(loss)
                        gnorms.append(gnorm)
                        if collect:
                            now = time.perf_counter()
                            step_times.append(now - t_step)
                            t_step = now
                            rt.tick(epoch=epoch, step=len(losses))
                    epoch_loss = (jnp.mean(jnp.stack(losses))
                                  if losses else 0.0)
                    epoch_gnorm = (jnp.mean(jnp.stack(gnorms))
                                   if gnorms else 0.0)
                    steps_run = len(losses)
                else:
                    losses, gnorms = [], []
                    workers = self._feed_workers()
                    # host_prefetch: worker-threaded gather/decode behind the
                    # double-buffered device prefetch — the loop below then
                    # receives already-sharded device batches and host_wait
                    # measures pure feed stall.  host_serial (always under a
                    # batch_hook): the classic gather->shard->step loop.
                    put = ((lambda b: mesh_lib.shard_batch(b, self.mesh))
                           if feed == "host_prefetch" else None)
                    # Host-side s2d only without a batch_hook: VAAL's hook
                    # feeds the same sharded batch to its 3-channel VAE.
                    feed_iter = iter(train_feed_batches(
                        train_set, labeled_idxs, bs, rng=rng, shuffle=True,
                        num_workers=workers,
                        prefetch=self.cfg.loader_tr.prefetch,
                        local=mesh_lib.process_local_rows(self.mesh, bs),
                        s2d=self._host_s2d and batch_hook is None,
                        put=put, depth=self.cfg.loader_tr.prefetch))
                    t_step = time.perf_counter() if collect else 0.0
                    while True:
                        t_wait = time.perf_counter() if collect else 0.0
                        item = next(feed_iter, None)
                        if item is None:
                            break
                        if collect:
                            # Time blocked on the feed (gather/decode on the
                            # serial leg, queue wait on the prefetched one):
                            # the numerator of feed_stall_frac.
                            host_waits.append(time.perf_counter() - t_wait)
                        with self.dispatch_lock:
                            sharded = (item if put is not None
                                       else mesh_lib.shard_batch(item,
                                                                 self.mesh))
                            state, key, loss, gnorm = self._chained_train_step(
                                state, sharded, key, lr, class_weights,
                                view=train_set.view)
                            self.dispatch_lock.drain(loss)
                        losses.append(loss)
                        gnorms.append(gnorm)
                        if batch_hook is not None:
                            # Receives the already-sharded device batch — no
                            # second host->device transfer on the hot path.
                            batch_hook(epoch, sharded)
                        if collect:
                            # Loop-cadence deltas (gather + dispatch; the
                            # donated-buffer backpressure makes steady-state
                            # cadence track real step time) — host-side, no
                            # sync.
                            now = time.perf_counter()
                            step_times.append(now - t_step)
                            t_step = now
                            rt.tick(epoch=epoch, step=len(losses))
                    epoch_loss = (jnp.mean(jnp.stack(losses))
                                  if losses else 0.0)
                    epoch_gnorm = (jnp.mean(jnp.stack(gnorms))
                                   if gnorms else 0.0)
                    steps_run = len(losses)
                record = {"epoch": epoch, "lr": float(lr),
                          "train_loss": epoch_loss, "grad_norm": epoch_gnorm}
                # steps_run: what the device executes — on every path
                # the real steps (the scan's bucket is only its shape).
                epoch_sp.args.update(steps_real=steps_run,
                                     steps_run=steps_run)
                if rows_are_tokens(train_set):
                    # Rows of token ids: what the epoch moved, in tokens.
                    epoch_sp.args["tokens"] = (
                        n_real * int(train_set.image_shape[0]))
                if state.counters:
                    # Still being computed: fetched with the losses in
                    # fit/finish and written into this span's record.
                    # (Copies: the next epoch donates the state.)
                    epoch_counts.append(
                        (epoch_sp, jax.tree.map(jnp.copy, state.counters)))

            if use_es:
                # Ends at the fetch of the counts — on the scan path this
                # is where the epoch's device time is waited for.
                with tracer.span("fit/validate"):
                    perf = self.evaluate(state, al_set, eval_idxs)
                    eval_acc = float(perf["accuracy"])
                    eval_top5 = float(perf["top_5_accuracy"])
                record.update(val_accuracy=eval_acc, val_top5=eval_top5)
                self.logger.info(
                    f"\tValidation performance on round {round_idx} at "
                    f"epoch {epoch} is {eval_acc * 100:.2f}%")
                # Per-epoch validation curves, like the reference's comet
                # logging (strategy.py:419-422) — the paper's curves need
                # every epoch, not a subsample.
                if metric_cb:
                    metric_cb(f"rd_{round_idx}_validation_accuracy",
                              eval_acc, epoch)
                    metric_cb(f"rd_{round_idx}_validation_top5_accuracy",
                              eval_top5, epoch)
                # >= : later epochs win ties (strategy.py:425-430).
                if eval_acc >= best_perf:
                    best_perf, best_epoch, es_count = eval_acc, epoch, 0
                    # Device-side snapshot (explicit copies: the train
                    # step donates its input buffers, so a bare reference
                    # would be invalidated next epoch).  The reference
                    # writes best_rd_{n}.pth on EVERY improvement
                    # (strategy.py:425-430); a full-variable device->host
                    # fetch + disk write per improving epoch dominates
                    # small-round epochs, so the host fetch is deferred
                    # to the periodic checkpoint cadence below and to the
                    # end of the fit — the on-disk best a resume consumes
                    # stays coherent with the fit state saved alongside.
                    best_variables = jax.tree.map(
                        jnp.copy, state.trainable_variables)
                    best_dirty, best_resumed = True, False
                    if on_best is not None:
                        try:
                            on_best(round_idx, epoch, full_variables(
                                best_variables, state.frozen))
                        except Exception:  # noqa: BLE001 - best-effort bus
                            self.logger.exception(
                                "on_best subscriber failed; continuing fit")
                else:
                    es_count += 1
                # The reference writes the latest ckpt every epoch
                # (strategy.py:440) and never consumes it; a full-variable
                # host transfer per epoch would dominate small-model epochs
                # on TPU, so write it periodically + on exit instead.
                if (weight_paths and mesh_lib.is_coordinator()
                        and epoch % self.current_ckpt_every == 0):
                    if best_dirty:
                        # Rank-0-style write guard (strategy.py:425-430);
                        # on a pod the ckpt_path must be a shared
                        # filesystem so every process can read it back.
                        # publish_best = atomic write + monotonic
                        # (round, best_epoch) tag for the concurrent
                        # readers (serve hot-reload, speculative scorer).
                        published = self._publish_best(
                            weight_paths, best_variables, round_idx,
                            best_epoch)
                        best_dirty = False
                    self._save_current(weight_paths,
                                       state.trainable_variables)
            if collect:
                # AFTER validation on purpose: on the epoch-scan path the
                # eval-accuracy fetch above is the sync that makes the
                # epoch wall real (see _emit_epoch_telemetry).
                self._emit_epoch_telemetry(
                    metric_cb, round_idx, epoch, n_epoch, n_real,
                    epoch_sp.duration_s,
                    time.perf_counter() - epoch_sp.t0, use_es,
                    steps_run, step_times)
                self._emit_feed_telemetry(
                    metric_cb, round_idx * (n_epoch + 1) + epoch,
                    host_waits, epoch_sp.duration_s)
                rt.tick(epoch=epoch, feed=feed)
            history.append(record)
            if use_es and es_count > es_patience:
                # Break BEFORE the periodic fit-state save: a state whose
                # es_count is already past patience must never persist —
                # resuming from it would train past the point where the
                # uninterrupted run stopped.
                self.logger.info("Early stopping criterion reached. ")
                break
            preempted = preempt_lib.requested() is not None
            if (weight_paths and batch_hook is None
                    and mesh_lib.is_coordinator()
                    and (epoch % self.current_ckpt_every == 0 or preempted)
                    and epoch < n_epoch):
                if preempted and best_dirty:
                    # The fit state about to be saved references
                    # best_epoch; without this publish the resumed fit
                    # would find best_ckpt missing and restart best-model
                    # tracking — diverging from the uninterrupted run.
                    published = self._publish_best(
                        weight_paths, best_variables, round_idx, best_epoch)
                    best_dirty = False
                with tracer.span("ckpt/save_fit_state", args={
                        "bytes": ckpt_lib.tree_bytes(
                            (state.trainable_variables, state.opt_state))}):
                    _CKPT_RETRY.call(
                        ckpt_lib.save_fit_state,
                        weight_paths["fit_state"],
                        variables=state.trainable_variables,
                        opt_state=state.opt_state, step=state.step,
                        epoch=epoch, round_idx=round_idx,
                        best_perf=best_perf, best_epoch=best_epoch,
                        es_count=es_count, key=key, rng=rng)
            if preempted:
                # Preemption (SIGTERM/SIGINT recorded by the driver's
                # handler): the epoch boundary is the safe point — the
                # fit state just saved (or the round-granular experiment
                # state, when this was the final epoch) resumes
                # bit-identically.  Raised AFTER the early-stop break
                # above, so a state past patience still never persists.
                preempt_lib.check()

        if best_variables is None:
            best_epoch = epochs_run
            best_variables = state.trainable_variables
            best_dirty = True
        if best_dirty and weight_paths and mesh_lib.is_coordinator():
            published = self._publish_best(weight_paths, best_variables,
                                           round_idx, best_epoch)
        if weight_paths and mesh_lib.is_coordinator():
            # The best IS the final state (always so without validation):
            # rd_{n} holds best_rd_{n}'s bytes, serialised once.
            same = published is not None and best_epoch == epochs_run
            self._save_current(weight_paths, state.trainable_variables,
                               data=published.data if same else None)
        with tracer.span("fit/finish"):
            if weight_paths and mesh_lib.is_coordinator():
                # The round completed: a later restart must re-run it from
                # scratch (the experiment-level resume owns cross-round
                # state).
                ckpt_lib.delete_fit_state(weight_paths["fit_state"])
            if mesh_lib.is_multiprocess(self.mesh):
                # Non-writer processes must not race ahead to read
                # best_ckpt (strategy.load_best_ckpt) before process 0
                # finishes writing.
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices("fit_ckpts_written")
            self.logger.info(
                f"Sanity Check: Best ckpt occurs on epoch {best_epoch}")
            seen: Dict[str, int] = {}
            for sp, counts in epoch_counts:
                # The state's counters run over the whole fit: an epoch's
                # own are the difference to the epoch before.
                total = {k: int(v) for k, v in counts.items()}
                tracer.amend(sp, **{k: v - seen.get(k, 0)
                                    for k, v in total.items()})
                seen = total
            ema_loss = ema_gnorm = None
            for rec in history:
                # Deferred train-loss fetch (see the epoch loop): one bulk
                # materialization here instead of one host sync per epoch.
                # The loss/grad-norm EMAs piggyback on this SAME fetch —
                # the telemetry rider costs no additional device sync.
                rec["train_loss"] = float(rec["train_loss"])
                rec["grad_norm"] = float(rec.get("grad_norm", 0.0))
                if collect and metric_cb is not None:
                    a = self.TELEMETRY_EMA_ALPHA
                    ema_loss = (rec["train_loss"] if ema_loss is None
                                else a * rec["train_loss"]
                                + (1 - a) * ema_loss)
                    ema_gnorm = (rec["grad_norm"] if ema_gnorm is None
                                 else a * rec["grad_norm"]
                                 + (1 - a) * ema_gnorm)
                    tele_step = round_idx * (n_epoch + 1) + rec["epoch"]
                    metric_cb("train_loss_ema", round(ema_loss, 6),
                              tele_step)
                    metric_cb("grad_norm_ema", round(ema_gnorm, 6),
                              tele_step)
        return FitResult(
            state=state, best_epoch=best_epoch, best_perf=best_perf,
            epochs_run=epochs_run, history=history,
            best_variables=None if best_resumed else best_variables,
            best_host=published.host if published is not None else None)
