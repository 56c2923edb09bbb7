"""JaxLearner + LearnerGroup: the SGD side of the RL stack.

Parity: rllib/core/learner/learner.py:170 (`Learner` — compute_loss :900,
update :1086) and learner_group.py:61 (`LearnerGroup`). The reference scales
SGD by DDP-wrapping N torch learner actors (torch_learner.py:212). TPU-native
stance: one learner process drives the whole device mesh (dp axis under pjit —
XLA inserts the grad allreduce over ICI); scaling out = a bigger mesh, not N
object-store-coupled actors. LearnerGroup therefore runs the learner either
in-process (mode="local") or as a single remote actor that owns the
accelerator (mode="remote", the IMPALA topology: CPU rollouts feed a TPU
learner).

The whole PPO update — epochs x shuffled minibatches — is ONE jitted call
(lax.scan over minibatch indices), so per-minibatch Python overhead is zero
and the step is a single XLA program on the mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.rllib.sample_batch import SampleBatch


class JaxLearner:
    """Holds train state and a jitted multi-epoch update.

    Subclasses define `loss_fn(params, minibatch) -> (loss, aux)` as a pure
    function; this base builds the optimizer, the scan-based update, and the
    weight/state plumbing.
    """

    def __init__(
        self,
        obs_dim: int,
        num_actions: int,
        hiddens: Sequence[int] = (64, 64),
        lr: float = 3e-4,
        grad_clip: float = 0.5,
        num_epochs: int = 10,
        minibatch_size: int = 128,
        seed: int = 0,
        mesh=None,
    ):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.rllib.models import mlp_actor_critic_init
        from ray_tpu.util.compile_cache import enable_compile_cache

        enable_compile_cache()
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.num_epochs = num_epochs
        self.minibatch_size = minibatch_size
        self.mesh = mesh
        self._optimizer = optax.chain(
            optax.clip_by_global_norm(grad_clip), optax.adam(lr)
        )
        params = mlp_actor_critic_init(
            jax.random.PRNGKey(seed), obs_dim, num_actions, hiddens
        )
        self._state = {
            "params": params,
            "opt_state": self._optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }
        self._rng = jax.random.PRNGKey(seed + 1)
        self._update_cache: Dict[int, Callable] = {}

    # -- subclass hook ------------------------------------------------------ #
    def loss_fn(self, params, minibatch) -> Tuple[Any, Dict[str, Any]]:
        raise NotImplementedError

    # -- update ------------------------------------------------------------- #
    def _build_update(self, batch_size: int):
        import jax
        import jax.numpy as jnp
        from jax import lax

        mb, epochs = self.minibatch_size, self.num_epochs
        num_mb = max(batch_size // mb, 1)
        mb_eff = min(mb, batch_size)
        optimizer = self._optimizer

        def minibatch_step(state, mb_idx, batch):
            minibatch = jax.tree.map(lambda x: x[mb_idx], batch)
            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(
                state["params"], minibatch
            )
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            import optax

            new_params = optax.apply_updates(state["params"], updates)
            new_state = {
                "params": new_params,
                "opt_state": new_opt,
                "step": state["step"] + 1,
            }
            aux = dict(aux, total_loss=loss, grad_norm=optax.global_norm(grads))
            return new_state, aux

        def update(state, batch, rng):
            def epoch_body(carry, key):
                state = carry
                perm = jax.random.permutation(key, batch_size)
                idx = perm[: num_mb * mb_eff].reshape(num_mb, mb_eff)
                state, auxes = lax.scan(
                    lambda s, i: minibatch_step(s, i, batch), state, idx
                )
                return state, auxes

            keys = jax.random.split(rng, epochs)
            state, auxes = lax.scan(epoch_body, state, keys)
            metrics = jax.tree.map(lambda x: jnp.mean(x), auxes)
            return state, metrics

        return jax.jit(update, donate_argnums=(0,))

    def update(self, batch: SampleBatch) -> Dict[str, float]:
        import jax

        n = len(batch)
        arrays = self._prepare_batch(batch)
        fn = self._update_cache.get(n)
        if fn is None:
            fn = self._update_cache[n] = self._build_update(n)
        self._rng, sub = jax.random.split(self._rng)
        self._state, metrics = fn(self._state, arrays, sub)
        out = {k: float(v) for k, v in metrics.items()}
        out["num_env_steps_trained"] = n
        return out

    def _prepare_batch(self, batch: SampleBatch):
        """Subclasses pick/transform columns; default passes float arrays."""
        return dict(batch)

    # -- state -------------------------------------------------------------- #
    def get_weights(self):
        import jax

        return jax.device_get(self._state["params"])

    def set_weights(self, params) -> None:
        self._state["params"] = params

    def get_state(self):
        import jax

        return jax.device_get(self._state)

    def set_state(self, state) -> None:
        self._state = state


class PPOLearner(JaxLearner):
    """Clipped-surrogate PPO loss (Schulman et al. 2017).

    Parity: rllib/algorithms/ppo/ppo_torch_policy.py loss — surrogate clip,
    value-function loss with clipping, entropy bonus, advantage
    standardization per train batch.
    """

    def __init__(
        self,
        *args,
        clip_param: float = 0.2,
        vf_clip_param: float = 10.0,
        vf_loss_coeff: float = 0.5,
        entropy_coeff: float = 0.01,
        **kwargs,
    ):
        self.clip_param = clip_param
        self.vf_clip_param = vf_clip_param
        self.vf_loss_coeff = vf_loss_coeff
        self.entropy_coeff = entropy_coeff
        super().__init__(*args, **kwargs)

    def _prepare_batch(self, batch: SampleBatch):
        import jax.numpy as jnp

        adv = np.asarray(batch[SampleBatch.ADVANTAGES], np.float32)
        adv = (adv - adv.mean()) / max(float(adv.std()), 1e-6)
        return {
            "obs": jnp.asarray(batch[SampleBatch.OBS], jnp.float32),
            "actions": jnp.asarray(batch[SampleBatch.ACTIONS]),
            "logp_old": jnp.asarray(batch[SampleBatch.ACTION_LOGP], jnp.float32),
            "vf_preds_old": jnp.asarray(batch[SampleBatch.VF_PREDS], jnp.float32),
            "advantages": jnp.asarray(adv),
            "value_targets": jnp.asarray(
                batch[SampleBatch.VALUE_TARGETS], jnp.float32
            ),
        }

    def loss_fn(self, params, mb):
        import jax.numpy as jnp

        from ray_tpu.rllib.models import (
            categorical_entropy,
            categorical_logp,
            mlp_actor_critic_apply,
        )

        logits, value = mlp_actor_critic_apply(params, mb["obs"])
        logp = categorical_logp(logits, mb["actions"])
        ratio = jnp.exp(logp - mb["logp_old"])
        adv = mb["advantages"]
        surrogate = jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1 - self.clip_param, 1 + self.clip_param) * adv,
        )
        policy_loss = -jnp.mean(surrogate)
        vf_err = jnp.clip(
            (value - mb["value_targets"]) ** 2, 0.0, self.vf_clip_param**2
        )
        vf_loss = jnp.mean(vf_err)
        entropy = jnp.mean(categorical_entropy(logits))
        total = (
            policy_loss + self.vf_loss_coeff * vf_loss - self.entropy_coeff * entropy
        )
        aux = {
            "policy_loss": policy_loss,
            "vf_loss": vf_loss,
            "entropy": entropy,
            "mean_kl": jnp.mean(mb["logp_old"] - logp),
        }
        return total, aux


class IMPALALearner(JaxLearner):
    """V-trace actor-critic loss (IMPALA, Espeholt et al. 2018).

    Parity: rllib/algorithms/impala/torch/impala_torch_learner.py — policy
    gradient with clipped importance weights, baseline loss against v-trace
    targets, entropy bonus. One pass over the whole time-major batch per
    update (no epochs/minibatches): the single jitted step keeps the learner
    hot while async actors stream batches at it.
    """

    def __init__(
        self,
        *args,
        gamma: float = 0.99,
        vf_loss_coeff: float = 0.5,
        entropy_coeff: float = 0.01,
        clip_rho_threshold: float = 1.0,
        clip_c_threshold: float = 1.0,
        **kwargs,
    ):
        self.gamma = gamma
        self.vf_loss_coeff = vf_loss_coeff
        self.entropy_coeff = entropy_coeff
        self.clip_rho_threshold = clip_rho_threshold
        self.clip_c_threshold = clip_c_threshold
        self._impala_update = None
        super().__init__(*args, **kwargs)

    def _build_impala_update(self):
        import jax
        import optax

        optimizer = self._optimizer
        loss_fn = self.loss_fn

        def update(state, batch):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch
            )
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            new_params = optax.apply_updates(state["params"], updates)
            new_state = {
                "params": new_params,
                "opt_state": new_opt,
                "step": state["step"] + 1,
            }
            aux = dict(aux, total_loss=loss, grad_norm=optax.global_norm(grads))
            return new_state, aux

        return jax.jit(update, donate_argnums=(0,))

    def update(self, batch: SampleBatch) -> Dict[str, float]:
        if self._impala_update is None:
            self._impala_update = self._build_impala_update()
        arrays = self._prepare_batch(batch)
        T, N = arrays["rewards"].shape
        self._state, metrics = self._impala_update(self._state, arrays)
        out = {k: float(v) for k, v in metrics.items()}
        out["num_env_steps_trained"] = T * N
        return out

    def _prepare_batch(self, batch: SampleBatch):
        import jax.numpy as jnp

        done = np.asarray(
            batch[SampleBatch.TERMINATEDS] | batch[SampleBatch.TRUNCATEDS]
        )
        return {
            "obs": jnp.asarray(batch[SampleBatch.OBS], jnp.float32),      # [T,N,D]
            "actions": jnp.asarray(batch[SampleBatch.ACTIONS]),           # [T,N]
            "behavior_logp": jnp.asarray(
                batch[SampleBatch.ACTION_LOGP], jnp.float32
            ),
            "rewards": jnp.asarray(batch[SampleBatch.REWARDS], jnp.float32),
            "discounts": jnp.asarray(
                self.gamma * (1.0 - done.astype(np.float32)), jnp.float32
            ),
            "bootstrap_obs": jnp.asarray(batch["_bootstrap_obs"], jnp.float32),
        }

    def loss_fn(self, params, mb):
        import jax.numpy as jnp

        from ray_tpu.rllib.models import (
            categorical_entropy,
            categorical_logp,
            mlp_actor_critic_apply,
        )
        from ray_tpu.rllib.vtrace import vtrace_from_logps

        T, N, D = mb["obs"].shape
        logits, values = mlp_actor_critic_apply(
            params, mb["obs"].reshape(T * N, D)
        )
        logits = logits.reshape(T, N, -1)
        values = values.reshape(T, N)
        target_logp = categorical_logp(logits, mb["actions"])
        bootstrap_value = mlp_actor_critic_apply(params, mb["bootstrap_obs"])[1]

        vt = vtrace_from_logps(
            behavior_logp=mb["behavior_logp"],
            target_logp=target_logp,
            rewards=mb["rewards"],
            values=values,
            bootstrap_value=bootstrap_value,
            discounts=mb["discounts"],
            clip_rho_threshold=self.clip_rho_threshold,
            clip_c_threshold=self.clip_c_threshold,
        )
        pg_loss = -jnp.mean(vt.pg_advantages * target_logp)
        vf_loss = 0.5 * jnp.mean((vt.vs - values) ** 2)
        entropy = jnp.mean(categorical_entropy(logits))
        total = (
            pg_loss + self.vf_loss_coeff * vf_loss - self.entropy_coeff * entropy
        )
        aux = {
            "policy_loss": pg_loss,
            "vf_loss": vf_loss,
            "entropy": entropy,
            "mean_rho": jnp.mean(
                jnp.exp(target_logp - mb["behavior_logp"])
            ),
        }
        return total, aux


class LearnerGroup:
    """Runs a learner in-process or as one remote accelerator-owning actor.

    Parity: rllib/core/learner/learner_group.py:61 — but see module docstring
    for why scale-out is mesh-width, not actor-count, on TPU.
    """

    def __init__(self, learner_cls, learner_kwargs: Dict[str, Any], mode: str = "local",
                 remote_options: Optional[Dict[str, Any]] = None):
        self.mode = mode
        if mode == "local":
            self._learner = learner_cls(**learner_kwargs)
            self._actor = None
        elif mode == "remote":
            import ray_tpu

            actor_cls = ray_tpu.remote(**(remote_options or {"num_cpus": 1}))(learner_cls)
            self._actor = actor_cls.remote(**learner_kwargs)
            self._learner = None
        else:
            raise ValueError(f"unknown LearnerGroup mode {mode!r}")

    def update(self, batch: SampleBatch) -> Dict[str, float]:
        if self._learner is not None:
            return self._learner.update(batch)
        import ray_tpu

        return ray_tpu.get(self._actor.update.remote(batch))

    def get_weights(self):
        if self._learner is not None:
            return self._learner.get_weights()
        import ray_tpu

        return ray_tpu.get(self._actor.get_weights.remote())

    def get_state(self):
        if self._learner is not None:
            return self._learner.get_state()
        import ray_tpu

        return ray_tpu.get(self._actor.get_state.remote())

    def set_state(self, state):
        if self._learner is not None:
            self._learner.set_state(state)
        else:
            import ray_tpu

            ray_tpu.get(self._actor.set_state.remote(state))

    def shutdown(self) -> None:
        """Kill the remote learner actor (it owns the accelerator — leaking it
        would keep the TPU locked for the next trial)."""
        if self._actor is not None:
            import ray_tpu

            try:
                ray_tpu.kill(self._actor)
            except Exception:  # noqa: BLE001 - already dead / shutdown race
                pass
            self._actor = None
