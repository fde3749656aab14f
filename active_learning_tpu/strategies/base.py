"""The active-learning Strategy engine.

TPU-native counterpart of the reference's ``Strategy`` base class
(src/query_strategies/strategy.py:21-485).  The reference interleaves pool
bookkeeping, DDP process management, training, evaluation, and checkpointing
in one 485-line class; here those concerns live in dedicated modules
(pool.PoolState, train.Trainer, train.checkpoint, utils.metrics) and
``Strategy`` composes them into the reference's public surface:

    query(budget) -> (labeled_idxs, cost)   [abstract; per-sampler]
    update(labeled_idxs, cost)              strategy.py:459-485
    init_network_weights()                  strategy.py:175-200
    train()                                 strategy.py:286-381
    load_best_ckpt()                        strategy.py:202-206
    test()                                  strategy.py:211-247

Key architectural differences (deliberate, TPU-first):
  * ONE persistent JAX runtime and mesh for the whole experiment — no
    per-round mp.spawn/NCCL process groups (strategy.py:288-315).
  * Pool scoring is mesh-parallel (strategies/scoring.py): the reference
    scores on a single GPU in the parent process (SURVEY.md §2 parallelism
    table).
  * All randomness flows from one np.random.Generator + JAX PRNG, so a
    round is exactly reproducible from saved state (the reference uses the
    global np.random / torch seeds).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import time
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

from .. import faults
from ..config import ExperimentConfig, TrainConfig
from ..data.core import Dataset
from ..models import backbone
from ..parallel import mesh as mesh_lib
from ..pool import PoolState
from ..registry import STRATEGIES
from ..telemetry import diagnostics as diag_lib
from ..telemetry import runtime as tele_runtime
from ..telemetry import spans as tele_spans
from ..train import checkpoint as ckpt_lib
from ..train.trainer import FitResult, Trainer, TrainState
from ..utils.logging import get_logger
from ..utils.metrics import MetricsSink, NullSink
from . import scoring

# Where this module compiles (al_lint recompile-hazard): the
# re-initialisation program, once per strategy.
_STEP_BUILDERS = ("_build_reinit", "_draw_frozen")

# Pool scoring is stateless (consumes no rng, reads frozen weights), so
# a whole-pass retry after a transient failure — a dead prefetch feeder
# thread, an injected feed_worker fault, a flaky H2D — reproduces the
# same scores bit for bit.  One retry: a pass that fails twice is not
# transient; the driver's degradation ladder takes over.
_SCORE_RETRY = faults.RetryPolicy(site="pool_score",
                                  classify=faults.classify_exception,
                                  max_attempts=2)


def _abstract_bytes(like: Dict) -> int:
    """Bytes of a flat tree of (abstract) leaves, from shapes."""
    return sum(math.prod(leaf.shape) * leaf.dtype.itemsize
               for leaf in like.values())


@dataclasses.dataclass
class KeptBest:
    """What this process's last fit left of its best epoch, beside the
    file: the tree on the device until ``load_best_ckpt`` installs it,
    and the one host copy ``ckpt/publish_best`` fetched.  ``state`` names
    the TrainState they belong to — the fit's result, then (``installed``)
    the state ``load_best_ckpt`` made of it, whose trainable leaves
    ``host`` then describes bit for bit — WEAKLY: the record must not
    keep a state's device arrays alive once the strategy has let go of
    it (the next re-initialisation)."""
    tag: Tuple[int, int]                  # (round, best_epoch)
    variables: Optional[Dict[str, Any]]   # None: the best is in the file
    host: Optional[Dict[str, Any]]        # None: this process wrote none
    state: "weakref.ReferenceType[TrainState]"
    installed: bool = False

    def belongs_to(self, state: Optional[TrainState]) -> bool:
        return state is not None and self.state() is state


class Strategy:
    """Base class: owns the model state, pool state, trainer, and metrics
    sink for one experiment; subclasses implement ``query``.

    Args mirror the reference constructor (strategy.py:74-124) in spirit:
    the dataset triple, the model + trainer, pool state, and configs.
    """

    def __init__(
        self,
        train_set: Dataset,
        al_set: Dataset,
        test_set: Optional[Dataset],
        model,
        trainer: Trainer,
        pool: PoolState,
        cfg: ExperimentConfig,
        train_cfg: TrainConfig,
        sink: Optional[MetricsSink] = None,
        rng: Optional[np.random.Generator] = None,
    ):
        self.train_set = train_set
        self.al_set = al_set
        self.test_set = test_set
        self.model = model
        self.trainer = trainer
        self.pool = pool
        self.cfg = cfg
        self.train_cfg = train_cfg
        self.sink = sink if sink is not None else NullSink()
        self.rng = rng if rng is not None else np.random.default_rng(cfg.run_seed)
        self.logger = get_logger()

        self.num_classes = al_set.num_classes
        self.mesh = trainer.mesh
        self.state: Optional[TrainState] = None
        self.best_epoch: int = 0
        self.best_perf: float = 0.0
        # The last fit's best weights where this process still holds
        # them (``_keep_fit``); None after a resume or a restart.
        self.kept_best: Optional[KeptBest] = None
        # The last test() accuracy — the driver's run_report rows read
        # it (test() already computes it; storing beats re-plumbing the
        # return through the round loop).
        self.last_test_acc: Optional[float] = None
        # The experiment-truth diagnostics layer (telemetry/diagnostics,
        # DESIGN.md §13): per-round score histograms + drift, selection
        # composition, pick distances, calibration — all computed from
        # host arrays that already exist.  None when disabled; every
        # hot-path hook below is then a single None check (<2.5µs/call,
        # pinned in tests/test_diagnostics.py), and picks/scores are
        # bit-identical either way.
        tele = getattr(cfg, "telemetry", None)
        self.diagnostics = (
            diag_lib.RoundDiagnostics(num_classes=self.num_classes)
            if tele is not None and getattr(tele, "enabled", False)
            and getattr(tele, "diagnostics", False) else None)
        # Device-resident pool cache: in-memory pool images live on device
        # for the WHOLE experiment (scoring.collect_pool fast path).  It
        # is the TRAINER'S cache, shared with evaluation, so one upload
        # serves every round's every sampler AND the per-epoch validation.
        self._resident_pool: Dict = trainer.resident_pool
        # True only for the first train() after a genuine experiment
        # resume (the driver sets it): that is the one fit allowed to
        # consume a mid-round fit state from disk; trainer.fit discards
        # stale states otherwise.
        self.resume_next_fit: bool = False
        # The pipelined-round coordinator (experiment/pipeline.py), or
        # None for the sequential loop.  The driver installs it; when
        # present, collect_scores consumes speculative chunk scores and
        # train() wires the best-ckpt publish into the fit.
        self.pipeline = None
        self._score_steps: Dict[str, Callable] = {}
        # Per-experiment init key; split once per re-init so every round's
        # random re-initialization is fresh but reproducible.
        self._init_key = jax.random.PRNGKey(int(self.rng.integers(2 ** 31)))
        # The re-initialisation program and its device-resident template
        # (init_network_weights builds both on its first call).
        self._reinit: Optional[Callable] = None
        self._reinit_template: Optional[Dict] = None
        self._frozen_like: Optional[Dict] = None

    # -- identity --------------------------------------------------------

    @property
    def round(self) -> int:
        return self.pool.round

    @round.setter
    def round(self, value: int) -> None:
        self.pool.round = int(value)

    @property
    def cumulative_cost(self) -> float:
        return self.pool.cumulative_cost

    @property
    def exp_hash(self) -> str:
        return self.cfg.exp_hash or "no_hash"

    # -- pool views (strategy.py:126-163) --------------------------------

    def available_query_idxs(self, shuffle: bool = True) -> np.ndarray:
        return self.pool.available_query_idxs(shuffle=shuffle, rng=self.rng)

    def available_query_mask(self) -> np.ndarray:
        return self.pool.available_mask()

    def already_labeled_idxs(self, shuffle: bool = False) -> np.ndarray:
        return self.pool.labeled_idxs(shuffle=shuffle, rng=self.rng)

    def already_labeled_mask(self) -> np.ndarray:
        return self.pool.labeled_mask()

    # -- weights (strategy.py:165-206) ------------------------------------

    def weight_paths(self) -> Dict[str, str]:
        return ckpt_lib.weight_paths(self.cfg.ckpt_path, self.cfg.exp_name,
                                     self.exp_hash, self.round)

    def init_network_weights(self) -> None:
        """The variables every round starts from (so the linear head
        always resets, strategy.py:182-184): the pretrained SSL/transfer
        checkpoint's leaves where one is configured (strategy.py:185-196),
        a fresh random draw for every leaf it does not cover — all from
        ONE compiled program whose inputs are the round's key and a
        device-resident template.  The file is read and overlaid only
        when the template is built.  The leaves the backbone declares
        frozen (models/backbone.py) are not among them: they are loaded
        once, beside the template, and every round's state holds those
        very arrays."""
        tracer = tele_spans.get_tracer()
        self._init_key, sub = jax.random.split(self._init_key)
        built = self._refresh_reinit_template(sub)
        template = self._reinit_template
        # Ends at an ENQUEUE: the program is asynchronous, so the copies
        # and draws it starts may finish under a later span.
        with tracer.span("reinit/apply", args={
                "template": "built" if built else "hit",
                "leaves_copied": len(template["leaves"]),
                "leaves_drawn": template["drawn"],
                "leaves_frozen": template["frozen_count"],
                "bytes": template["bytes"]}):
            variables = self._reinit(sub, template["leaves"])
            if self.state is None:
                self.state = self.trainer.state_of(
                    variables, frozen=template["frozen"])
            else:
                self.state = self.state.replace(
                    params=variables["params"],
                    batch_stats=variables.get("batch_stats", {}),
                    frozen=template["frozen"])
        if self.train_cfg.has_pretrained:
            self.logger.info(
                f"Initialized network weights from "
                f"{self.train_cfg.pretrained.path}")
        else:
            self.logger.info("Initialized Network Weights Randomly.")

    def skeleton_state(self) -> TrainState:
        """A state to load a checkpoint into (experiment resume): the
        template's leaves under a throwaway key, the frozen leaves as
        every round holds them.  Consumes no split of ``_init_key``."""
        key = jax.random.PRNGKey(0)
        self._refresh_reinit_template(key)
        template = self._reinit_template
        return self.trainer.state_of(
            self._reinit(key, template["leaves"]), frozen=template["frozen"])

    def _init_variables(self, key):
        """``model.init`` on a zero row, traced: the parameter and
        statistics collections (what a forward sows is no variable)."""
        shape = self.train_set.gather(np.zeros(1, dtype=np.int64)).shape
        variables = self.model.init(key, jnp.zeros(shape, jnp.float32),
                                    train=False)
        return {c: variables[c] for c in ("params", "batch_stats")
                if c in variables}

    def _build_reinit(self) -> Callable:
        """``reinit(key, template) -> variables``, compiled once: the
        TRAINABLE leaves only.  The covered set is read off the template
        itself (its paths are part of the jit's cache key): a leaf the
        template holds is a COPY of it — no donation: the fit donates the
        state it is given, and the template must outlive it — and every
        other leaf keeps ``model.init``'s draw, so XLA drops the forward
        pass and the draws nothing reads (the frozen leaves' among them)
        as dead code.  ``out_shardings`` pins the REPLICATED layout the
        epoch program was compiled against (the lesson of
        Trainer.reinit_optimizer)."""
        prefixes = backbone.frozen_prefixes(self.model)

        @functools.partial(
            jax.jit, out_shardings=mesh_lib.replicated_sharding(self.mesh))
        def reinit(key, template):
            flat = {path: leaf for path, leaf in flatten_dict(
                        self._init_variables(key)).items()
                    if not backbone.is_frozen_path(path, prefixes)}
            flat.update(template)
            return unflatten_dict(flat)
        tele_runtime.get_run().register_jit(f"reinit@{id(self):x}", reinit)
        return reinit

    def _refresh_reinit_template(self, key: jax.Array) -> bool:
        """The re-initialisation program (built on the first call) and
        its template: the leaves the pretrained checkpoint covers,
        overlaid on the host ONCE and kept replicated on the mesh — the
        trainable ones under ``leaves`` (copied every round), the frozen
        ones under ``frozen`` (``encoder/load``: read, uploaded and, where
        the file does not cover one, drawn, once).  It is rebuilt only
        when the file's (path, mtime, size) has changed — one ``os.stat``
        a round honours a replaced file as the reference's
        read-every-round would.  True when it was built."""
        cfg = self.train_cfg.pretrained
        stamp = None
        if self.train_cfg.has_pretrained:
            st = os.stat(cfg.path)
            stamp = (cfg.path, st.st_mtime_ns, st.st_size)
        if self._reinit is None:
            self._reinit = self._build_reinit()
        elif self._reinit_template["stamp"] == stamp:
            return False
        tracer = tele_spans.get_tracer()
        prefixes = backbone.frozen_prefixes(self.model)
        # The model's leaves in the abstract (no draw, nothing fetched).
        like = flatten_dict(jax.eval_shape(self._reinit, key, {}))
        if prefixes and self._frozen_like is None:
            self._frozen_like = {
                p: v for p, v in flatten_dict(jax.eval_shape(
                    self._init_variables, key)).items() if p not in like}
        frozen_like = self._frozen_like or {}
        every = {**like, **frozen_like}
        leaves, frozen = {}, {}
        load_span = (tracer.span("encoder/load", args={
            "leaves": len(frozen_like), "bytes": _abstract_bytes(frozen_like)})
            if frozen_like else contextlib.nullcontext())
        with load_span:
            if stamp is not None:
                from ..utils import pretrained as pretrained_lib
                with tracer.span("reinit/pretrained_read"):
                    torch_state = pretrained_lib.load_torch_state_dict(
                        cfg.path)
                covered = pretrained_lib.pretrained_leaves(
                    every, cfg, torch_state,
                    key_map=getattr(self.model, "torch_key_to_flax", None))
                leaves = {p: v for p, v in covered.items() if p in like}
                frozen = mesh_lib.replicate(
                    {p: v for p, v in covered.items() if p in frozen_like},
                    self.mesh)
                del covered, torch_state
            if len(frozen) < len(frozen_like):
                frozen.update(self._draw_frozen(
                    tuple(p for p in frozen_like if p not in frozen)))
        if leaves:
            # Key surgery and the torch->flax mapping are behind us: the
            # one upload of the leaves every round copies.
            with tracer.span("reinit/overlay"):
                leaves = mesh_lib.replicate(leaves, self.mesh)
        self._reinit_template = {
            "stamp": stamp, "leaves": leaves,
            "drawn": len(like) - len(leaves),
            "bytes": _abstract_bytes(like),
            "frozen": unflatten_dict(frozen).get("params", {}),
            "frozen_count": len(frozen_like)}
        return True

    def _draw_frozen(self, paths: Tuple[Tuple[str, ...], ...]) -> Dict:
        """The frozen leaves no checkpoint covers, drawn ONCE: from the
        run's seed and not from the round's key, so that a resumed run
        holds the encoder the first run held."""
        @functools.partial(
            jax.jit, out_shardings=mesh_lib.replicated_sharding(self.mesh))
        def draw_frozen(key):
            flat = flatten_dict(self._init_variables(key))
            return {p: flat[p] for p in paths}
        return draw_frozen(jax.random.fold_in(
            jax.random.PRNGKey(int(self.cfg.run_seed) % (2 ** 31)), 0xF02E))

    def _keep_fit(self, result: FitResult) -> None:
        """The fit's outcome: its final state, and its best weights kept
        under the tag ``best_ckpt`` was published with."""
        self.state = result.state
        self.best_epoch = result.best_epoch
        self.kept_best = KeptBest(
            tag=(self.round, result.best_epoch),
            variables=result.best_variables, host=result.best_host,
            state=weakref.ref(result.state))

    def load_best_ckpt(self) -> None:
        """The state the round goes on with holds the fit's best epoch.
        Where this process's own fit of THIS round left that tree on the
        device and nothing has replaced the state since, it is installed
        as it is: the bits ``best_ckpt`` was written from, no file read
        and no transfer.  Otherwise (experiment resume, a mid-round
        resume whose best epoch ran in an earlier process, a service
        restart, a state set from outside) the file is read."""
        path = self.weight_paths()["best_ckpt"]
        kept = self.kept_best
        on_device = (kept is not None and kept.variables is not None
                     and kept.tag == (self.round, self.best_epoch)
                     and kept.belongs_to(self.state))
        self.logger.info(
            "Installing the fit's best weights (on the device)"
            if on_device else f"Loading best ckpt so far from: {path}")
        like = self.state.trainable_variables
        with tele_spans.get_tracer().span(
                "ckpt/load_best",
                args={"bytes": ckpt_lib.tree_bytes(like),
                      "source": "device" if on_device else "file"}):
            if on_device:
                self.state = self.state.replace(
                    params=kept.variables["params"],
                    batch_stats=kept.variables["batch_stats"])
                # The state holds them now: no second reference keeps
                # them alive past the next re-initialisation.
                kept.variables, kept.state = None, weakref.ref(self.state)
                kept.installed = True
            else:
                self.kept_best = None
                variables = ckpt_lib.load_variables(path, like=like)
                self.state = self.trainer.replace_variables(self.state,
                                                            variables)

    def host_variables(self) -> Optional[Dict[str, Any]]:
        """The host copy of ``state.trainable_variables`` where one
        already exists (``ckpt/publish_best`` fetched it and
        ``load_best_ckpt`` installed the tree it was fetched from); None
        once anything has replaced the state."""
        kept = self.kept_best
        if kept is not None and kept.installed and kept.belongs_to(self.state):
            return kept.host
        return None

    # -- auxiliary round-level state (resume seam) ------------------------

    def aux_state_bytes(self) -> Optional[bytes]:
        """Serialized sampler-owned state beyond the pool/model (e.g.
        VAAL's VAE+discriminator) for the round-level experiment save.
        None = nothing to persist.  The reference keeps such state for
        free by pickling the whole strategy object
        (src/utils/resume_training.py:38-52)."""
        return None

    def restore_aux_state(self, data: bytes) -> None:
        """Inverse of aux_state_bytes, called during experiment resume."""

    # -- the four verbs ---------------------------------------------------

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        raise NotImplementedError

    def speculative_scoring_plan(self) -> Optional[Dict]:
        """The NEXT query's scoring pass as a plan the pipelined round's
        speculative scorer can run ahead of time, or None when there is
        nothing safely speculable.

        Contract (experiment/pipeline.py): the plan must be computed
        WITHOUT consuming any rng and must name EXACTLY the
        (kind, keys, idxs) the coming ``query`` will hand to
        ``collect_scores`` — the pipeline serves the speculative result
        only on an exact match, so a wrong plan degrades to the
        sequential pass, never to a wrong score.  Samplers whose scored
        index order is rng-dependent (partitioned variants, subset
        caps) or who score with non-checkpoint state (VAAL's VAE)
        return None.  Keys: ``kind`` (a _get_score_step name), ``keys``
        (tuple), ``idxs`` (int64 array)."""
        return None

    def update(self, labeled_idxs, cur_cost: float) -> None:
        """Mark queried examples labeled, spend budget, emit the audit
        trail (strategy.py:459-485)."""
        labeled_idxs = np.asarray(labeled_idxs, dtype=np.int64).reshape(-1)
        # Selection composition (class balance / novelty) must read the
        # labeled mask BEFORE this update flips it; one gated call.
        self._record_pick_diagnostics(labeled_idxs)
        self.pool.update(labeled_idxs, cur_cost)
        self.sink.log_metric("cumulative_budget", self.pool.cumulative_cost,
                             step=self.round)
        self.logger.info(
            f"Cumulative budget used on round {self.round} = "
            f"{self.pool.cumulative_cost}")
        self.sink.log_asset(f"labeled_idxs_on_rd_{self.round}",
                            ",".join(str(int(e)) for e in labeled_idxs))

    def train(self) -> None:
        """Per-round training with validation + early stopping.  The mesh
        is persistent — this replaces the whole mp.spawn/DDP stack
        (strategy.py:286-381)."""
        if self.state is None:
            self.init_network_weights()
        labeled = self.already_labeled_idxs()
        self.logger.info(f"Starting training on round {self.round}")
        if self.pipeline is not None:
            # The select-time prefetch must never run INTO the fit it
            # warmed — on the last round (which never arms) this is the
            # only join.
            self.pipeline.join_prefetch()

        def metric_cb(name: str, value: float, step: int) -> None:
            self.sink.log_metric(name, value, step=step)

        result = self.trainer.fit(
            self.state,
            self.train_set,
            labeled,
            self.al_set,
            self.pool.eval_idxs,
            n_epoch=self.cfg.n_epoch,
            es_patience=self.cfg.early_stop_patience,
            rng=self.rng,
            round_idx=self.round,
            weight_paths=self.weight_paths(),
            metric_cb=metric_cb,
            resume_fit_state=self.resume_next_fit,
            # The in-process leg of the best-ckpt bus: the pipelined
            # round's speculative scorer starts on a new best the moment
            # it is snapshotted, without waiting for the periodic disk
            # publish.
            on_best=(self.pipeline.publish_best
                     if self.pipeline is not None else None),
        )
        self.resume_next_fit = False
        if self.pipeline is not None:
            # Pin the FINAL (round, best_epoch) tag: speculative chunks
            # scored from any other checkpoint are now dead, and the
            # scorer keeps working from the final one through
            # load_best_ckpt/test until the next query consumes it.
            self.pipeline.finalize(self.round, result.best_epoch)
        self._keep_fit(result)
        # The fit's best validation accuracy: collapse detectors (e.g.
        # the evidence protocol's re-init guard,
        # scripts/cifar10_evidence.py) read it to tell a dead round —
        # best-of-fit at chance — from a trained one.
        self.best_perf = float(result.best_perf)
        self.logger.info(f"Finished training on round {self.round}")

    def test(self) -> Optional[float]:
        """Test-set evaluation + the reference's metric schema: round- and
        budget-keyed accuracy plus the per-class asset
        (strategy.py:211-247)."""
        if self.test_set is None:
            self.logger.info("Skipped testing loop, no testing dataset found.")
            return None
        with tele_spans.get_tracer().span("test/evaluate"):
            perf = self.trainer.evaluate(self.state, self.test_set,
                                         np.arange(len(self.test_set)))
        acc = float(perf["accuracy"])
        self.last_test_acc = acc
        # Calibration (ECE + confidence histogram) piggybacks on the
        # eval pass's additive per-bin counts — no second pass.
        self._record_calibration_diagnostics(perf)
        top5 = float(perf["top_5_accuracy"])
        byclass = np.asarray(perf["accuracy_byclass"])
        order = np.argsort(byclass)
        k = int(min(5, len(byclass)))
        self.logger.info(
            f"Test performance at round {self.round} is {acc * 100:.2f}%")
        self.logger.info(
            f"Best {k} classes: "
            f"{ {int(i): f'{byclass[i] * 100:.2f}' for i in order[-k:]} }")
        self.logger.info(
            f"Worst {k} classes: "
            f"{ {int(i): f'{byclass[i] * 100:.2f}' for i in order[:k]} }")
        self.logger.info(
            f"Test top 5 acc at round {self.round} is {top5 * 100:.2f}%")
        self.sink.log_metrics(
            {"rd_test_accuracy": acc, "rd_test_top5_accuracy": top5},
            step=self.round)
        self.sink.log_metrics(
            {"budget_test_accuracy": acc, "budget_test_top5_accuracy": top5},
            step=self.pool.cumulative_cost)
        self.sink.log_asset(
            f"test_acc_byclass_rd_{self.round}",
            ",".join(f"{e:.2f}" for e in byclass))
        return acc

    # -- scoring infrastructure -------------------------------------------

    def _score_batch_size(self) -> int:
        """Global scoring batch: explicit config wins; auto keeps the
        reference's test-loader batch on CPU and raises it to a
        row-size-scaled per-chip floor on accelerators (see
        Trainer.eval_batch_size — scoring is per-example under eval BN,
        so this is throughput-only)."""
        explicit = self.train_cfg.score_batch_size
        if explicit:
            return self.trainer.padded_batch_size(int(explicit))
        # Auto: ONE policy with evaluation (Trainer.eval_batch_size) —
        # the floor must never diverge between the two passes.
        return self.trainer.padded_batch_size(
            self.trainer.eval_batch_size(self.al_set))

    def _get_score_step(self, kind: str) -> Callable:
        if kind not in self._score_steps:
            view = self.al_set.view
            if kind == "prob_stats":
                self._score_steps[kind] = scoring.make_prob_stats_step(
                    self.model, view)
            elif kind == "embed":
                self._score_steps[kind] = scoring.make_embed_step(
                    self.model, view)
            elif kind == "embed_margin":
                self._score_steps[kind] = scoring.make_embed_step(
                    self.model, view, with_probs=True)
            elif kind == "mase":
                self._score_steps[kind] = scoring.make_mase_step(
                    self.model, view)
            elif kind == "badge":
                self._score_steps[kind] = scoring.make_badge_step(
                    self.model, view)
            elif kind == "badge_pool":
                self._score_steps[kind] = scoring.make_badge_step(
                    self.model, view, pool_512=True)
            else:
                raise KeyError(f"unknown scoring kind '{kind}'")
            # Compile accounting (telemetry/runtime.py): scoring steps
            # join the trainer's in the generalized jit-cache counter —
            # a nonzero per-round miss delta after round 1 is a shape
            # leak.  No-op without an installed run.
            tele_runtime.get_run().register_jit(
                f"score_{kind}@{id(self):x}", self._score_steps[kind])
        return self._score_steps[kind]

    def collect_scores(self, idxs: np.ndarray, kind: str,
                       keys=None) -> Dict[str, np.ndarray]:
        """Mesh-parallel scoring pass over ``al_set[idxs]`` returning host
        arrays aligned with ``idxs``.  With telemetry on, the pass's
        pool-scan rate lands in the sink as ``pool_rows_per_sec`` —
        the acquisition-side counterpart of the trainer's imgs_per_sec.

        Under a pipelined round the speculative scorer is consulted
        first: chunks it pre-scored with the FINAL best checkpoint are
        served as-is and the rest are completed inline — bit-identical
        either way (experiment/pipeline.py's correctness contract), so
        speculation only ever changes wall-clock."""
        bs = self._score_batch_size()
        if self.pipeline is not None:
            out = self.pipeline.consume(kind, keys, np.asarray(idxs), bs,
                                        self.state.variables)
            if out is not None:
                # Score histogram from the consume path's per-chunk
                # partials (bit-equal to the monolithic add — pinned).
                self._record_score_diagnostics(
                    out, self.pipeline.last_consume.get("score_hist"))
                if tele_runtime.get_run().train_metrics:
                    self.sink.log_metric(
                        "spec_hit_frac",
                        self.pipeline.last_consume.get("hit_frac", 0.0),
                        step=self.round)
                    # The same scan-rate metric the sequential pass
                    # emits, over the scoring COMPUTE the hand-over
                    # actually cost (served chunks' scorer walls +
                    # inline completions) — most of it hidden in the
                    # fit, but the rate stays comparable across modes.
                    score_s = self.pipeline.last_consume.get("score_s", 0)
                    if score_s > 0:
                        self.sink.log_metric(
                            "pool_rows_per_sec",
                            round(len(idxs) / score_s, 1),
                            step=self.round)
                return out
        loader = self.train_cfg.loader_te
        t0 = time.perf_counter()
        out = _SCORE_RETRY.call(
            scoring.collect_pool,
            self.al_set, idxs, bs,
            self._get_score_step(kind), self.state.variables, self.mesh,
            num_workers=loader.num_workers, prefetch=loader.prefetch,
            keys=keys, dispatch_lock=self.trainer.dispatch_lock,
            **self._resident_kwargs())
        dt = time.perf_counter() - t0
        if tele_runtime.get_run().train_metrics and dt > 0:
            self.sink.log_metric("pool_rows_per_sec",
                                 round(len(idxs) / dt, 1), step=self.round)
        self._record_score_diagnostics(out)
        return out

    # -- experiment-truth diagnostics hooks (telemetry/diagnostics) -------
    #
    # Each hook is ONE flag check when diagnostics are off (the pinned
    # <2.5µs/call off-path bound) and pure host-array math when on — the
    # diagnostics-inert lint (scripts/al_lint.py) statically forbids
    # anything heavier from growing here.

    def _record_score_diagnostics(self, out: Dict[str, np.ndarray],
                                  premerged=None) -> None:
        """Fold a scoring pass's scalar acquisition scores into the
        round's histogram.  ``premerged``: the pipelined consume path's
        per-chunk partial sums ({key: ScoreHistogram}), used as-is."""
        if self.diagnostics is None:
            return
        key = diag_lib.primary_score_key(out)
        if key is None:
            return
        if premerged is not None and key in premerged:
            self.diagnostics.observe_histogram(key, premerged[key])
        else:
            self.diagnostics.observe_scores(key, out[key])

    def _record_pick_dist_diagnostics(self, dists) -> None:
        """k-center pick distances, straight out of the selection scan
        (strategies/kcenter.LAST_PICK_DISTS)."""
        if self.diagnostics is None or dists is None:
            return
        self.diagnostics.observe_pick_dists(dists)

    def _record_pick_diagnostics(self, labeled_idxs: np.ndarray) -> None:
        """Selection composition for this round's picks (class balance
        and novelty need oracle labels — simulated AL always has them)."""
        if self.diagnostics is None or len(labeled_idxs) == 0:
            return
        targets = getattr(self.al_set, "targets", None)
        if targets is not None:
            targets = np.asarray(targets)[:len(self.al_set)]
        self.diagnostics.observe_picks(labeled_idxs, targets,
                                       self.pool.labeled_mask())

    def _record_calibration_diagnostics(self, perf: Dict) -> None:
        if self.diagnostics is None or "cal_count" not in perf:
            return
        self.diagnostics.observe_calibration(
            perf["cal_count"], perf["cal_correct"], perf["cal_conf_sum"])

    def _resident_kwargs(self) -> Dict:
        """collect_pool kwargs for the device-resident pool: one gating
        convention (a resolved budget of 0 disables) for every sampler,
        including VAAL's own scoring pass.  The budget is the TRAINER'S
        resolved one (auto-sized from HBM headroom when the config is
        None — pool residency is the default, not an override), and the
        host fallback pre-transforms batches for s2d-stem models."""
        rb = self.trainer.resident_budget
        # A pool pinned before an auto-budget refresh shrank rb to 0 must
        # keep its fast path (same rule as trainer.evaluate): its bytes
        # stay in HBM either way, so streaming would pay twice.
        have_pinned = bool(self._resident_pool.get("images"))
        return {"resident_cache": (self._resident_pool
                                   if rb or have_pinned else None),
                "resident_max_bytes": rb,
                "host_s2d": getattr(self.model, "stem",
                                    "default") == "s2d",
                # The trainer's resolved resident layout (DESIGN.md
                # §2b): every sampler's scoring pass pins/reads the
                # shared pool in the SAME layout training does.
                "pool_sharding": self.trainer.pool_sharding}


def register_strategy(name: str):
    """Decorator: register a Strategy subclass under its reference name
    (replaces the eval()-based get_strategy, get_strategy.py:16-17)."""

    def deco(cls):
        STRATEGIES.register(name, cls)
        cls.name = name
        return cls

    return deco
