"""VAAL: Variational Adversarial Active Learning (arXiv:1904.00370).

Reference: src/query_strategies/vaal_sampler.py:15-280.  A VAE and a latent
discriminator co-train alongside the classifier; acquisition picks the
points the discriminator scores most-likely-unlabeled.

Per training batch, three updates (vaal_train, :185-274):
  1. classifier SGD step on the labeled batch (shared with the base
     Trainer);
  2. VAE step: recon+KLD on the labeled batch, the same transductively on
     an unlabeled batch, plus ``adversary_param`` x BCE pushing the
     discriminator to call BOTH batches labeled;
  3. discriminator step on freshly-encoded (post-update) latents: labeled
     -> 1, unlabeled -> 0.

TPU design: steps 2+3 are ONE jitted function over the sharded batch pair
(the heavy compute is the VAE convs — mesh data parallelism comes from the
batch sharding like every other step); the classifier step and all
validation / early-stopping / checkpoint bookkeeping are reused from
Trainer.fit via its ``batch_hook`` seam instead of re-implementing the
whole epoch loop (the reference copies ~100 lines of parallel_train_fn).

Reference quirks preserved:
  * one crop window shared by every VAE forward of a step (the per-batch
    np.random seed, :214, vae.py:62-78);
  * the discriminator step re-encodes with the JUST-updated VAE, in train
    mode, so BN stats advance on those forwards too (:251-253);
  * the KL term is SUMMED over batch and latent dims while the recon MSE
    is a mean (vae_loss, :276-280);
  * both aux optimizers are Adam but follow the classifier's epoch LR
    schedule shape (:139-144).

Divergence (documented): the reference hard-maps num_classes 10/1000 to a
latent scale and rejects anything else (:23-29); here the VAE crop adapts
to the image size (64 for >=64px inputs, else the full image — any size
divisible by 16), which reproduces both reference cases exactly.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from ..data.augment import apply_view
from ..data.pipeline import iterate_batches
from ..models.vaal import VAE, Discriminator, crop_size_for, random_crop
from ..parallel import mesh as mesh_lib
from ..train.optim import make_lr_schedule
from . import scoring
from .base import Strategy, register_strategy

# Registered step-builders (scripts/al_lint.py recompile-hazard): both
# jitted steps are built once per sampler and reused across epochs.
_STEP_BUILDERS = ("_build_vaal_step", "_build_score_step")

# Donating callables stored on attributes (al_lint donation-safety):
# the co-training step donates the VAALState at position 0 — every call
# site must rebind self.vaal_state from the result in the same
# statement or the lint flags a use-after-donate.
_DONATES = {"_vaal_step": (0,)}


class VAALState(struct.PyTreeNode):
    vae_params: dict
    vae_stats: dict
    vae_opt: tuple
    d_params: dict
    d_opt: tuple


def _masked_mse(recon, x, mask):
    per_row = jnp.mean((recon - x) ** 2, axis=(1, 2, 3))
    return jnp.sum(per_row * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _masked_kld(mu, logvar, mask):
    # Reference sums over batch AND latent dims (vaal_sampler.py:278-279).
    per_row = -0.5 * jnp.sum(1 + logvar - mu ** 2 - jnp.exp(logvar), axis=1)
    return jnp.sum(per_row * mask)


def _masked_bce(preds, target, mask):
    p = jnp.clip(preds.reshape(-1), 1e-7, 1 - 1e-7)
    per = -(target * jnp.log(p) + (1.0 - target) * jnp.log(1.0 - p))
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@register_strategy("VAALSampler")
class VAALSampler(Strategy):

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        vcfg = self.cfg.vaal
        hw = self.al_set.image_shape[0]
        self.crop = crop_size_for(hw)
        if self.crop % 16 != 0:
            raise ValueError(
                f"VAAL needs an input crop divisible by 16, got {self.crop}")
        self.vae = VAE(z_dim=vcfg.vae_latent_dim, nc=3, crop=self.crop)
        self.disc = Discriminator(z_dim=vcfg.vae_latent_dim)
        self.adversary_param = float(vcfg.adversary_param)
        self.lr_vae_at = make_lr_schedule(self.train_cfg.scheduler,
                                          vcfg.lr_vae)
        self.lr_d_at = make_lr_schedule(self.train_cfg.scheduler,
                                        vcfg.lr_discriminator)
        self._tx_vae = optax.scale_by_adam()
        self._tx_d = optax.scale_by_adam()
        self.vaal_state: VAALState = None
        self._vaal_step = self._build_vaal_step()
        self._score_step = self._build_score_step()

    # -- state ------------------------------------------------------------

    def _init_vaal_state(self, key: jax.Array) -> VAALState:
        k_vae, k_d = jax.random.split(key)
        x = jnp.zeros((2, self.crop, self.crop, 3), jnp.float32)
        vae_vars = self.vae.init(k_vae, x, train=False)
        d_params = self.disc.init(
            k_d, jnp.zeros((2, self.cfg.vaal.vae_latent_dim)))["params"]
        state = VAALState(
            vae_params=vae_vars["params"],
            vae_stats=vae_vars["batch_stats"],
            vae_opt=self._tx_vae.init(vae_vars["params"]),
            d_params=d_params,
            d_opt=self._tx_d.init(d_params))
        return mesh_lib.replicate(state, self.mesh)

    def init_network_weights(self) -> None:
        """Classifier re-init + fresh VAE/discriminator every round
        (vaal_sampler.py:72-75)."""
        super().init_network_weights()
        self._init_key, sub = jax.random.split(self._init_key)
        self.vaal_state = self._init_vaal_state(sub)

    # -- round-level resume (the reference gets this via whole-object
    # pickle, resume_training.py:38-52; here the seam is explicit) --------

    def aux_state_bytes(self):
        if self.vaal_state is None:
            return None
        from flax import serialization
        return serialization.to_bytes(
            jax.tree.map(np.asarray, self.vaal_state))

    def restore_aux_state(self, data: bytes) -> None:
        from flax import serialization
        # Template with the right treedef/shapes; its values are fully
        # overwritten.  PRNGKey(0) here does NOT touch _init_key, so the
        # restored key stream continues exactly as the uninterrupted run.
        template = jax.tree.map(np.asarray,
                                self._init_vaal_state(jax.random.PRNGKey(0)))
        restored = serialization.from_bytes(template, data)
        self.vaal_state = mesh_lib.replicate(restored, self.mesh)

    # -- the jitted co-training step --------------------------------------

    def _build_vaal_step(self):
        vae, disc = self.vae, self.disc
        tx_vae, tx_d = self._tx_vae, self._tx_d
        adversary = self.adversary_param
        view = self.train_set.view
        crop = self.crop

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(vs: VAALState, batch_l, batch_u, key, lr_vae, lr_d):
            ks = jax.random.split(key, 7)
            x_l = apply_view(batch_l["image"], view, key=ks[0], train=True)
            x_u = apply_view(batch_u["image"], view, key=ks[1], train=True)
            # Same window for labeled AND unlabeled (see module docstring).
            x_l = random_crop(x_l, crop, ks[2])
            x_u = random_crop(x_u, crop, ks[2])
            m_l, m_u = batch_l["mask"], batch_u["mask"]

            def vae_loss_fn(vae_params):
                v = {"params": vae_params, "batch_stats": vs.vae_stats}
                (recon_l, _, mu_l, lv_l), mut = vae.apply(
                    v, x_l, ks[3], train=True, mutable=["batch_stats"])
                v = {"params": vae_params,
                     "batch_stats": mut["batch_stats"]}
                (recon_u, _, mu_u, lv_u), mut = vae.apply(
                    v, x_u, ks[4], train=True, mutable=["batch_stats"])
                unsup = _masked_mse(recon_l, x_l, m_l) + _masked_kld(
                    mu_l, lv_l, m_l)
                trans = _masked_mse(recon_u, x_u, m_u) + _masked_kld(
                    mu_u, lv_u, m_u)
                d_l = disc.apply({"params": vs.d_params}, mu_l)
                d_u = disc.apply({"params": vs.d_params}, mu_u)
                adv = _masked_bce(d_l, 1.0, m_l) + _masked_bce(d_u, 1.0, m_u)
                return unsup + trans + adversary * adv, mut["batch_stats"]

            (vae_loss, vae_stats), grads = jax.value_and_grad(
                vae_loss_fn, has_aux=True)(vs.vae_params)
            upd, vae_opt = tx_vae.update(grads, vs.vae_opt, vs.vae_params)
            vae_params = optax.apply_updates(
                vs.vae_params, jax.tree.map(lambda u: -lr_vae * u, upd))

            # Discriminator step on post-update latents, train-mode
            # forwards (BN stats advance — reference :251-253).
            v = {"params": vae_params, "batch_stats": vae_stats}
            (_, _, mu_l, _), mut = vae.apply(v, x_l, ks[5], train=True,
                                             mutable=["batch_stats"])
            v = {"params": vae_params, "batch_stats": mut["batch_stats"]}
            (_, _, mu_u, _), mut = vae.apply(v, x_u, ks[6], train=True,
                                             mutable=["batch_stats"])
            mu_l = jax.lax.stop_gradient(mu_l)
            mu_u = jax.lax.stop_gradient(mu_u)

            def d_loss_fn(d_params):
                d_l = disc.apply({"params": d_params}, mu_l)
                d_u = disc.apply({"params": d_params}, mu_u)
                return (_masked_bce(d_l, 1.0, m_l)
                        + _masked_bce(d_u, 0.0, m_u))

            d_loss, d_grads = jax.value_and_grad(d_loss_fn)(vs.d_params)
            upd, d_opt = tx_d.update(d_grads, vs.d_opt, vs.d_params)
            d_params = optax.apply_updates(
                vs.d_params, jax.tree.map(lambda u: -lr_d * u, upd))

            new_state = VAALState(vae_params=vae_params,
                                  vae_stats=mut["batch_stats"],
                                  vae_opt=vae_opt, d_params=d_params,
                                  d_opt=d_opt)
            return new_state, {"vae_loss": vae_loss, "d_loss": d_loss}

        return step

    # -- training ---------------------------------------------------------

    def train(self) -> None:
        """Trainer.fit drives the classifier exactly as the base Strategy;
        the batch hook runs the VAE+discriminator co-step on each labeled
        batch paired with a cycling unlabeled batch
        (vaal_train, vaal_sampler.py:185-274)."""
        if self.state is None:
            self.init_network_weights()
        if self.vaal_state is None:
            self._init_key, sub = jax.random.split(self._init_key)
            self.vaal_state = self._init_vaal_state(sub)
        labeled = self.already_labeled_idxs()
        bs = self.trainer.padded_batch_size(
            self.train_cfg.loader_tr.batch_size)
        hook_key = jax.random.PRNGKey(int(self.rng.integers(2 ** 31)))

        unlabeled_iter_holder = {"iter": None}

        def next_unlabeled_batch():
            it = unlabeled_iter_holder["iter"]
            batch = next(it, None) if it is not None else None
            if batch is None:
                unlabeled = self.available_query_idxs(shuffle=True)
                if len(unlabeled) == 0:  # pool exhausted: recycle labeled
                    unlabeled = labeled
                unlabeled_iter_holder["iter"] = iterate_batches(
                    self.train_set, unlabeled, bs,
                    local=mesh_lib.process_local_rows(self.mesh, bs))
                batch = next(unlabeled_iter_holder["iter"])
            return batch

        def metric_cb(name: str, value: float, step: int) -> None:
            self.sink.log_metric(name, value, step=step)

        def batch_hook(epoch: int, sharded_batch: Dict) -> None:
            nonlocal hook_key
            batch_u = next_unlabeled_batch()
            hook_key, sub = jax.random.split(hook_key)
            lr_vae = jnp.float32(self.lr_vae_at(epoch - 1))
            lr_d = jnp.float32(self.lr_d_at(epoch - 1))
            self.vaal_state, _ = self._vaal_step(
                self.vaal_state, sharded_batch,
                mesh_lib.shard_batch(batch_u, self.mesh),
                sub, lr_vae, lr_d)

        self.logger.info(f"Starting training on round {self.round}")
        result = self.trainer.fit(
            self.state, self.train_set, labeled, self.al_set,
            self.pool.eval_idxs, n_epoch=self.cfg.n_epoch,
            es_patience=self.cfg.early_stop_patience, rng=self.rng,
            round_idx=self.round, weight_paths=self.weight_paths(),
            metric_cb=metric_cb, batch_hook=batch_hook)
        self._keep_fit(result)
        self.logger.info(f"Finished training on round {self.round}")

    # -- acquisition ------------------------------------------------------

    def _build_score_step(self):
        vae, disc = self.vae, self.disc
        view = self.al_set.view
        crop = self.crop
        crop_key = jax.random.PRNGKey(0)  # deterministic window at scoring

        @jax.jit
        def step(variables, batch):
            x = apply_view(batch["image"], view, train=False)
            x = random_crop(x, crop, crop_key)
            v = {"params": variables["vae_params"],
                 "batch_stats": variables["vae_stats"]}
            _, _, mu, _ = vae.apply(v, x, None, train=False)
            preds = disc.apply({"params": variables["d_params"]}, mu)
            return {"d_score": preds.reshape(-1)}

        return step

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        """Lowest discriminator score first — the points the adversary is
        most confident are unlabeled (vaal_sampler.py:39-70)."""
        idxs = self.available_query_idxs(shuffle=False)
        if len(idxs) == 0:
            return idxs, 0
        if self.vaal_state is None:
            # Only reachable resuming a save that predates aux-state
            # persistence: score with a fresh adversary rather than crash,
            # but say so — this round's picks differ from an uninterrupted
            # run's.
            self.logger.warning(
                "VAAL aux state missing from the resumed experiment; "
                "initializing a fresh VAE/discriminator for this query")
            self._init_key, sub = jax.random.split(self._init_key)
            self.vaal_state = self._init_vaal_state(sub)
        variables = {"vae_params": self.vaal_state.vae_params,
                     "vae_stats": self.vaal_state.vae_stats,
                     "d_params": self.vaal_state.d_params}
        loader = self.train_cfg.loader_te
        resident_kwargs = self._resident_kwargs()
        # VAAL scores with its VAE/discriminator, not the classifier: the
        # VAE is 3-channel, so an s2d-stem classifier must not switch the
        # host feed to space-to-depth batches here.
        resident_kwargs["host_s2d"] = False
        out = scoring.collect_pool(
            self.al_set, idxs, self._score_batch_size(), self._score_step,
            variables, self.mesh, num_workers=loader.num_workers,
            prefetch=loader.prefetch, **resident_kwargs)
        budget = int(min(len(idxs), budget))
        order = np.argsort(out["d_score"], kind="stable")[:budget]
        self.logger.info(f"Number of queried images: {budget}")
        return idxs[order], budget
