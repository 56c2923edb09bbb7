"""Tune layer tests: search spaces, ASHA pruning, PBT exploits, Tuner API.

Parity model: tune/tests/ — scheduler simulations with mock trainables
(SURVEY.md §4.5). The PBT test is the VERDICT round-2 "done" bar: PBT mutates
hyperparams across >= 8 concurrent trials and Tuner(JaxTrainer).fit() runs.
"""

import itertools

import numpy as np
import pytest

from ray_tpu.tune import (
    ASHAScheduler,
    PopulationBasedTraining,
    Trainable,
    TuneConfig,
    Tuner,
    choice,
    grid_search,
    loguniform,
    uniform,
)
from ray_tpu.tune.search import BasicVariantGenerator
from ray_tpu.tune.trial import ERROR, TERMINATED


class TestSearchSpaces:
    def test_grid_cross_product_and_samples(self):
        gen = BasicVariantGenerator(
            {"a": grid_search([1, 2, 3]), "b": grid_search(["x", "y"]),
             "c": uniform(0, 1), "fixed": 7},
            num_samples=2, seed=0,
        )
        configs = list(gen.configs())
        assert len(configs) == 12  # 3 * 2 grid, x2 samples
        assert {(c["a"], c["b"]) for c in configs} == {
            (a, b) for a in (1, 2, 3) for b in ("x", "y")
        }
        assert all(0 <= c["c"] <= 1 and c["fixed"] == 7 for c in configs)

    def test_loguniform_range(self):
        gen = BasicVariantGenerator({"lr": loguniform(1e-5, 1e-1)},
                                    num_samples=50, seed=1)
        vals = [c["lr"] for c in gen.configs()]
        assert all(1e-5 <= v <= 1e-1 for v in vals)
        # log-spread: both decades below 1e-3 and above should appear
        assert any(v < 1e-3 for v in vals) and any(v > 1e-3 for v in vals)


class _Quadratic(Trainable):
    """score climbs toward -(x-3)^2 asymptotically; good x → good score."""

    def step(self):
        x = self.config["x"]
        target = -((x - 3.0) ** 2)
        score = target * (1 - 0.5 ** self.iteration if self.iteration else 0.0)
        return {"score": target - abs(target) * 0.5 ** (self.iteration + 1)}


class _CheckpointedCounter(Trainable):
    def setup(self, config):
        self.total = 0.0

    def step(self):
        self.total += self.config.get("increment", 1.0)
        return {"score": self.total}

    def save_checkpoint(self, checkpoint_dir):
        return {"total": self.total}

    def load_checkpoint(self, checkpoint):
        self.total = checkpoint["total"]

    def reset_config(self, new_config):
        self.config = dict(new_config)
        return True


class TestTunerLocal:
    def test_grid_search_finds_best(self, ray_start_local):
        tuner = Tuner(
            _Quadratic,
            param_space={"x": grid_search([0.0, 1.0, 3.0, 5.0])},
            tune_config=TuneConfig(metric="score", mode="max", num_samples=1),
            run_config=_stop(training_iteration=3),
        )
        grid = tuner.fit()
        assert len(grid) == 4
        best = grid.get_best_result()
        assert best.config["x"] == 3.0

    def test_function_trainable(self, ray_start_local):
        def objective(config):
            return {"score": -(config["x"] - 2.0) ** 2, "done": True}

        grid = Tuner(
            objective,
            param_space={"x": grid_search([0.0, 2.0])},
            tune_config=TuneConfig(metric="score", mode="max"),
        ).fit()
        assert grid.get_best_result().config["x"] == 2.0

    def test_trial_error_isolated(self, ray_start_local):
        def sometimes_fails(config):
            if config["x"] == 1:
                raise RuntimeError("boom")
            return {"score": config["x"], "done": True}

        grid = Tuner(
            sometimes_fails,
            param_space={"x": grid_search([0, 1, 2])},
            tune_config=TuneConfig(metric="score", mode="max"),
        ).fit()
        assert grid.num_errors == 1
        assert grid.get_best_result().config["x"] == 2


class TestASHA:
    def test_bad_trials_stopped_early(self, ray_start_local):
        scheduler = ASHAScheduler(max_t=16, grace_period=2, reduction_factor=2)
        tuner = Tuner(
            _Quadratic,
            param_space={"x": grid_search([0.0, 0.5, 1.0, 2.5, 3.0, 3.5, 5.0, 6.0])},
            tune_config=TuneConfig(
                metric="score", mode="max", scheduler=scheduler,
                max_concurrent_trials=8,
            ),
            run_config=_stop(training_iteration=16),
        )
        grid = tuner.fit()
        iters = {t.config["x"]: t.iteration for t in grid}
        # the best configs survive to max_t; the worst are cut early
        assert iters[3.0] == 16
        assert iters[6.0] < 16
        assert grid.get_best_result().config["x"] == 3.0


class TestPBT:
    def test_exploit_mutates_and_clones(self, ray_start_local, monkeypatch):
        """8 concurrent trials, served in turn; bottom trials must adopt top
        checkpoints (score jumps to cloned total) and mutated hyperparams."""
        import ray_tpu

        # The controller serves whichever trial reports first, and PBT can
        # only exploit when a whole population is scored at an interval: on
        # a loaded box one trial ran its ten iterations before a second had
        # reported, and nothing was exploited. Here the refs are ready in
        # turn, so the order is the same on every box.
        turn = itertools.count()
        first_ready = ray_tpu.wait

        def ready_in_turn(refs, num_returns=1, timeout=None):
            mine = refs[next(turn) % len(refs)]
            first_ready([mine], num_returns=1, timeout=timeout)
            return [mine], [r for r in refs if r is not mine]

        monkeypatch.setattr(ray_tpu, "wait", ready_in_turn)
        scheduler = PopulationBasedTraining(
            perturbation_interval=2,
            hyperparam_mutations={"increment": [0.25, 0.5, 1.0, 2.0, 4.0]},
            quantile_fraction=0.25,
            seed=0,
        )
        incs = [0.25, 0.25, 0.5, 0.5, 1.0, 1.0, 2.0, 4.0]
        tuner = Tuner(
            _CheckpointedCounter,
            param_space={"increment": grid_search(incs)},
            tune_config=TuneConfig(
                metric="score", mode="max", scheduler=scheduler,
                max_concurrent_trials=8,
            ),
            run_config=_stop(training_iteration=10),
        )
        grid = tuner.fit()
        assert grid.num_errors == 0
        assert scheduler.num_perturbations >= 1
        # at least one trial's config was mutated away from its grid value
        mutated = [
            t for t, inc0 in zip(grid.trials, incs)
            if t.config["increment"] != inc0
        ]
        assert mutated, "PBT never exploited"
        # exploited trials cloned a better total: their final score must
        # exceed what their original increment alone could produce
        best = grid.get_best_result()
        assert best.metric("score") >= 4.0 * 2  # top increment for >=2 iters


def _stop(**criteria):
    class _RC:
        stop = dict(criteria)

    return _RC()


class TestTunerOverJaxTrainer:
    def test_tuner_wraps_jax_trainer(self, ray_start_regular):
        """Tuner(JaxTrainer).fit() runs trials that each do a tiny jax train
        loop through the Train layer (VERDICT round-2 'done' bar)."""
        from ray_tpu.train import JaxTrainer, ScalingConfig
        from ray_tpu.train.session import report

        def train_loop(config):
            import jax
            import jax.numpy as jnp

            lr = config["lr"]
            w = jnp.zeros(())
            for step in range(3):
                g = 2 * (w - 1.0)
                w = w - lr * g
                report({"loss": float((w - 1.0) ** 2), "lr": lr})

        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"lr": 0.1},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=False),
        )
        grid = Tuner(
            trainer,
            param_space={"lr": grid_search([0.1, 0.5])},
            tune_config=TuneConfig(metric="loss", mode="min"),
        ).fit()
        assert len(grid) == 2
        assert grid.num_errors == 0
        best = grid.get_best_result()
        assert best.config["lr"] == 0.5


class TestHyperBandAndMedian:
    def test_hyperband_brackets_prune_and_keep_best(self, ray_start_local):
        """Bracketed async halving: the best config survives to max_t, bad
        ones are cut early, and trials actually spread across >1 bracket."""
        from ray_tpu.tune import HyperBandScheduler

        scheduler = HyperBandScheduler(max_t=16, grace_period=2,
                                       reduction_factor=2)
        assert len(scheduler.brackets) > 1  # a real bracket portfolio
        tuner = Tuner(
            _Quadratic,
            param_space={"x": grid_search(
                [0.0, 0.5, 1.0, 2.5, 3.0, 3.5, 5.0, 6.0])},
            tune_config=TuneConfig(
                metric="score", mode="max", scheduler=scheduler,
                max_concurrent_trials=8,
            ),
            run_config=_stop(training_iteration=16),
        )
        grid = tuner.fit()
        iters = {t.config["x"]: t.iteration for t in grid}
        assert iters[3.0] == 16                  # the optimum survives
        assert min(iters.values()) < 16          # something was pruned
        assert len(set(scheduler._trial_bracket.values())) > 1
        assert grid.get_best_result().config["x"] == 3.0

    def test_median_stopping_rule(self, ray_start_local):
        """Trials whose running mean is below the peer median stop early;
        above-median trials run to completion."""
        from ray_tpu.tune import MedianStoppingRule

        scheduler = MedianStoppingRule(grace_period=3, min_samples_required=3)
        tuner = Tuner(
            _Quadratic,
            param_space={"x": grid_search(
                [0.0, 1.0, 2.5, 3.0, 3.5, 5.0, 6.0, 7.0])},
            tune_config=TuneConfig(
                metric="score", mode="max", scheduler=scheduler,
                max_concurrent_trials=8,
            ),
            run_config=_stop(training_iteration=12),
        )
        grid = tuner.fit()
        iters = {t.config["x"]: t.iteration for t in grid}
        assert iters[3.0] == 12                  # near-optimum never stopped
        assert iters[7.0] < 12                   # far-off config cut early
        assert grid.get_best_result().config["x"] == 3.0
