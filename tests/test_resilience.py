"""Fault tolerance (scaletorch_tpu/resilience.py + integrations).

Three layers of coverage, all in the quick tier:

  * unit — DivergenceSentinel policies, retry_with_backoff,
    PreemptionHandler, FaultInjector, ResilienceManager protocol, and the
    in-jit non-finite update guard (trainer/train_step.guarded_update).
  * CheckpointManager hardening — injected save failures retried with
    backoff, exhausted retries never raising, async->sync degradation,
    corrupted-latest fallback to the previous step.
  * end-to-end inject -> recover — a ``ToyTrainer`` that keeps the REAL
    ``Trainer.train`` loop, rollback, emergency-checkpoint and save/load
    code and swaps only the mesh/SPMD step for a tiny jit model (the 5D
    SPMD step needs newer JAX than the quick-tier container provides;
    the full-Trainer variants live in
    tests/trainer/test_resilient_trainer.py under the slow marker).
"""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.config import ScaleTorchTPUArguments
from scaletorch_tpu.resilience import (
    DivergenceSentinel,
    FaultInjector,
    PreemptionHandler,
    ResilienceManager,
    TrainingDivergedError,
    retry_with_backoff,
)

# ---------------------------------------------------------------------------
# DivergenceSentinel
# ---------------------------------------------------------------------------


class TestDivergenceSentinel:
    def test_healthy_losses_feed_ema(self):
        s = DivergenceSentinel(ema_beta=0.5)
        assert s.observe(4.0) == "ok"
        assert s.observe(2.0) == "ok"
        assert s.ema == pytest.approx(3.0)
        assert s.total_anomalies == 0

    def test_nonfinite_is_anomalous_and_skips(self):
        s = DivergenceSentinel(policy="skip")
        s.observe(4.0)
        assert s.observe(float("nan")) == "skip"
        assert s.observe(float("inf")) == "skip"
        assert s.nonfinite_losses == 2
        # anomalies never feed the EMA
        assert s.ema == pytest.approx(4.0)

    def test_spike_detection_needs_warm_ema(self):
        s = DivergenceSentinel(policy="skip", spike_factor=2.0)
        assert s.observe(100.0) == "ok"  # first loss warms the EMA
        assert s.observe(50.0) == "ok"
        assert s.observe(1000.0) == "skip"
        assert s.loss_spikes == 1

    def test_abort_policy_raises_immediately(self):
        s = DivergenceSentinel(policy="abort")
        s.observe(1.0)
        with pytest.raises(TrainingDivergedError, match="abort"):
            s.observe(float("nan"))

    def test_consecutive_anomalies_abort_any_policy(self):
        s = DivergenceSentinel(policy="skip", max_consecutive_anomalies=3)
        s.observe(1.0)
        assert s.observe(float("nan")) == "skip"
        assert s.observe(float("nan")) == "skip"
        with pytest.raises(TrainingDivergedError, match="consecutive"):
            s.observe(float("nan"))

    def test_healthy_step_resets_consecutive(self):
        s = DivergenceSentinel(policy="skip", max_consecutive_anomalies=2)
        s.observe(1.0)
        s.observe(float("nan"))
        s.observe(1.0)
        assert s.consecutive == 0
        s.observe(float("nan"))  # starts a fresh streak, below the cap
        assert s.total_anomalies == 2

    def test_rollback_budget_aborts_before_the_excess_restore(self):
        s = DivergenceSentinel(policy="rollback", max_rollbacks=2)
        s.ensure_rollback_budget()
        s.note_rollback()
        s.ensure_rollback_budget()
        s.note_rollback()
        # the abort fires BEFORE rollback #3 performs its restore
        with pytest.raises(TrainingDivergedError, match="rollback"):
            s.ensure_rollback_budget()

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            DivergenceSentinel(policy="explode")

    def test_counters_shape(self):
        s = DivergenceSentinel()
        s.observe(1.0)
        s.observe(float("nan"))
        assert s.counters() == {
            "anomalies": 1.0, "nonfinite_losses": 1.0,
            "loss_spikes": 0.0, "rollbacks": 0.0,
        }


# ---------------------------------------------------------------------------
# retry_with_backoff
# ---------------------------------------------------------------------------


class TestRetryWithBackoff:
    def test_succeeds_after_transient_failures(self):
        calls, sleeps = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "done"

        out = retry_with_backoff(
            flaky, retries=3, base_delay=0.25, jitter=0.0,
            sleep=sleeps.append,
        )
        assert out == "done"
        assert len(calls) == 3
        # exponential: 0.25 then 0.5
        assert sleeps == pytest.approx([0.25, 0.5])

    def test_exhausted_retries_reraise(self):
        sleeps = []
        with pytest.raises(OSError, match="persistent"):
            retry_with_backoff(
                lambda: (_ for _ in ()).throw(OSError("persistent")),
                retries=2, base_delay=0.01, sleep=sleeps.append,
            )
        assert len(sleeps) == 2

    def test_delay_capped_and_jittered(self):
        sleeps = []
        calls = []

        def fail_then_ok():
            calls.append(1)
            if len(calls) < 5:
                raise OSError("x")
            return 1

        retry_with_backoff(
            fail_then_ok, retries=4, base_delay=1.0, max_delay=2.0,
            jitter=0.5, sleep=sleeps.append,
        )
        assert all(d <= 2.0 * 1.5 for d in sleeps)
        assert sleeps[2] >= 2.0  # capped base, pre-jitter >= max_delay

    def test_non_retriable_passes_through(self):
        with pytest.raises(KeyboardInterrupt):
            retry_with_backoff(
                lambda: (_ for _ in ()).throw(KeyboardInterrupt()),
                retries=5, base_delay=0.01, sleep=lambda _: None,
            )


# ---------------------------------------------------------------------------
# PreemptionHandler
# ---------------------------------------------------------------------------


class TestPreemptionHandler:
    def test_real_sigterm_sets_flag_and_uninstall_restores(self):
        prev = signal.getsignal(signal.SIGTERM)
        h = PreemptionHandler()
        with h:
            assert not h.requested
            os.kill(os.getpid(), signal.SIGTERM)
            assert h.requested
            assert h.signum == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) is prev

    def test_second_sigint_falls_through_to_keyboardinterrupt(self):
        h = PreemptionHandler()
        h.trigger(signal.SIGINT)
        assert h.requested
        with pytest.raises(KeyboardInterrupt):
            h.trigger(signal.SIGINT)

    def test_sigterm_then_one_sigint_stays_graceful(self):
        # only REPEATED SIGINTs escalate; SIGTERM + one ctrl-C must still
        # get the graceful emergency-checkpoint path
        h = PreemptionHandler()
        h.trigger(signal.SIGTERM)
        h.trigger(signal.SIGINT)  # must NOT raise
        assert h.requested

    def test_trigger_simulates_without_real_signal(self):
        h = PreemptionHandler()
        h.trigger()
        assert h.requested


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------


class TestFaultInjector:
    def test_nan_fires_once_at_step(self):
        inj = FaultInjector(nan_at_step=3)
        m = inj.corrupt_metrics(2, {"loss": 1.0})
        assert m["loss"] == 1.0
        m = inj.corrupt_metrics(3, {"loss": 1.0})
        assert np.isnan(m["loss"])
        # rollback re-reaches step 3: must not fire twice
        m = inj.corrupt_metrics(3, {"loss": 1.0})
        assert m["loss"] == 1.0

    def test_save_failures_consumed(self):
        inj = FaultInjector(fail_saves=2)
        assert inj.take_save_failure()
        assert inj.take_save_failure()
        assert not inj.take_save_failure()

    def test_from_config_env_overrides(self, monkeypatch):
        cfg = ScaleTorchTPUArguments(ft_nan_at_step=5)
        inj = FaultInjector.from_config(cfg)
        assert inj.nan_at_step == 5
        monkeypatch.setenv("SCALETORCH_TPU_FT_NAN_STEP", "9")
        assert FaultInjector.from_config(cfg).nan_at_step == 9

    def test_env_zero_cancels_config_armed_drill(self, monkeypatch):
        # a PRESENT env var wins even at 0, so a restarted job can cancel
        # a drill baked into its config without a config edit
        cfg = ScaleTorchTPUArguments(ft_sigterm_at_step=100)
        monkeypatch.setenv("SCALETORCH_TPU_FT_SIGTERM_STEP", "0")
        assert FaultInjector.from_config(cfg).sigterm_at_step == 0

    def test_inactive_by_default(self):
        assert not FaultInjector().active


# ---------------------------------------------------------------------------
# ResilienceManager protocol
# ---------------------------------------------------------------------------


class TestResilienceManager:
    def test_ok_path_untouched(self):
        rm = ResilienceManager(sentinel=DivergenceSentinel())
        m, action = rm.after_step(1, {"loss": 2.0})
        assert action == "ok" and m["loss"] == 2.0

    def test_skip_on_injected_nan(self):
        rm = ResilienceManager(
            sentinel=DivergenceSentinel(policy="skip"),
            injector=FaultInjector(nan_at_step=2),
        )
        rm.after_step(1, {"loss": 2.0})
        m, action = rm.after_step(2, {"loss": 2.0})
        assert action == "skip" and np.isnan(m["loss"])

    def test_rollback_callback_invoked_and_counted(self):
        rm = ResilienceManager(sentinel=DivergenceSentinel(policy="rollback"))
        rm.after_step(1, {"loss": 2.0})
        rolled = []
        _, action = rm.after_step(
            2, {"loss": float("nan")},
            rollback=lambda: rolled.append(1) or True,
        )
        assert action == "rollback" and rolled
        assert rm.sentinel.rollbacks == 1

    def test_rollback_without_checkpoint_downgrades_to_skip(self):
        rm = ResilienceManager(sentinel=DivergenceSentinel(policy="rollback"))
        rm.after_step(1, {"loss": 2.0})
        _, action = rm.after_step(2, {"loss": float("nan")},
                                  rollback=lambda: False)
        assert action == "skip"
        assert rm.sentinel.rollbacks == 0

    def test_from_config_disabled_sentinel(self):
        cfg = ScaleTorchTPUArguments(sentinel_frequency=0)
        rm = ResilienceManager.from_config(cfg)
        assert rm.sentinel is None
        m, action = rm.after_step(1, {"loss": float("nan")})
        assert action == "ok"  # host sentinel off; in-jit guard still runs

    def test_injected_nan_observed_even_off_sample_cadence(self):
        # a drill must not be silently ignored because its step doesn't
        # land on the sentinel's sampling cadence
        rm = ResilienceManager(
            sentinel=DivergenceSentinel(policy="skip"),
            injector=FaultInjector(nan_at_step=3),
            sentinel_frequency=10,
        )
        _, a = rm.after_step(1, {"loss": 1.0})
        assert a == "ok"  # off-cadence, not sampled
        m, a = rm.after_step(3, {"loss": 1.0})
        assert a == "skip" and np.isnan(m["loss"])

    def test_from_config_default_follows_log_frequency(self):
        # -1 (default) resolves to the logging cadence, where the loss
        # host-sync is already paid — no extra sync on the hot path
        cfg = ScaleTorchTPUArguments(log_frequency=10)
        rm = ResilienceManager.from_config(cfg)
        assert rm.sentinel_frequency == 10
        assert ResilienceManager.from_config(
            ScaleTorchTPUArguments(log_frequency=10, sentinel_frequency=1)
        ).sentinel_frequency == 1


# ---------------------------------------------------------------------------
# In-jit non-finite update guard (shared by spmd.py via guarded_update)
# ---------------------------------------------------------------------------

V, H, SEQ = 32, 8, 16


def toy_forward(params, ids, cfg, positions=None, attention_backend=None,
                gradient_checkpointing=False, **kw):
    """make_train_step's model contract on a 2-matrix toy LM."""
    return params["embed"][ids] @ params["head"]


def toy_params(scale=0.1, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "embed": jax.random.normal(k1, (V, H), jnp.float32) * scale,
        "head": jax.random.normal(k2, (H, V), jnp.float32) * scale,
    }


def toy_batch(rng, accum=2, micro=2):
    toks = rng.integers(0, V, size=(accum, micro, SEQ + 1)).astype(np.int32)
    return {
        "input_ids": toks[:, :, :-1],
        "target_ids": toks[:, :, 1:],
        "position_ids": np.broadcast_to(
            np.arange(SEQ, dtype=np.int32), (accum, SEQ)).copy(),
    }


class TestNonfiniteGuard:
    def _step(self, **kw):
        from scaletorch_tpu.trainer.optimizer import create_optimizer
        from scaletorch_tpu.trainer.train_step import make_train_step

        args = ScaleTorchTPUArguments(learning_rate=1e-2)
        tx, _ = create_optimizer(args)
        return tx, make_train_step(toy_forward, object(), tx, donate=False,
                                   **kw)

    def test_finite_step_updates_and_reports_zero(self):
        tx, step = self._step()
        p = toy_params()
        o = tx.init(p)
        rng = np.random.default_rng(0)
        p2, o2, m = step(p, o, toy_batch(rng))
        assert float(m["update_skipped"]) == 0.0
        assert np.isfinite(float(m["loss"]))
        assert not np.allclose(p["embed"], p2["embed"])

    def test_nonfinite_loss_freezes_params_and_opt_state(self):
        tx, step = self._step()
        # poison ONE param so loss/grads are NaN inside the jitted step
        p = toy_params()
        p = {**p, "head": p["head"].at[0, 0].set(jnp.nan)}
        o = tx.init(toy_params())  # finite optimizer state
        rng = np.random.default_rng(0)
        p2, o2, m = step(p, o, toy_batch(rng))
        assert float(m["update_skipped"]) == 1.0
        # params bit-identical (update rejected); float opt state
        # (moments) frozen; INTEGER state (schedule counts) advances so
        # lr schedules stay aligned with the trainer's global_step
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        saw_count = False
        for a, b in zip(jax.tree.leaves(o), jax.tree.leaves(o2)):
            a, b = np.asarray(a), np.asarray(b)
            if np.issubdtype(b.dtype, np.integer):
                np.testing.assert_array_equal(a + 1, b)
                saw_count = True
            else:
                np.testing.assert_array_equal(a, b)
        assert saw_count  # adamw carries a schedule count

    def test_guard_off_keeps_legacy_metrics(self):
        tx, step = self._step(nonfinite_guard=False)
        p = toy_params()
        rng = np.random.default_rng(0)
        _, _, m = step(p, tx.init(p), toy_batch(rng))
        assert set(m) == {"loss", "grad_norm"}


# ---------------------------------------------------------------------------
# CheckpointManager hardening
# ---------------------------------------------------------------------------


def small_tree():
    return {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}


class TestCheckpointRetries:
    def _cm(self, tmp_path, **kw):
        from scaletorch_tpu.utils.checkpoint import CheckpointManager

        kw.setdefault("retry_base_delay", 0.01)
        return CheckpointManager(str(tmp_path), async_save=False, **kw)

    def test_injected_failures_are_retried(self, tmp_path):
        inj = FaultInjector(fail_saves=2)
        cm = self._cm(tmp_path, retries=3, fault_injector=inj)
        assert cm.save(1, params=small_tree(), opt_state=small_tree())
        cm.wait()
        assert cm.all_steps() == [1]

    def test_exhausted_retries_return_false_not_raise(self, tmp_path):
        inj = FaultInjector(fail_saves=100)
        cm = self._cm(tmp_path, retries=2, fault_injector=inj)
        assert cm.save(1, params=small_tree(), opt_state=small_tree()) is False
        assert cm.all_steps() == []

    def test_async_failure_degrades_to_sync(self, tmp_path):
        from scaletorch_tpu.utils.checkpoint import CheckpointManager

        cm = CheckpointManager(str(tmp_path), async_save=True,
                               retries=1, retry_base_delay=0.01)
        broken = cm._mgr

        def boom(*a, **kw):
            raise RuntimeError("async pool died")

        broken.save = boom
        assert cm.save(1, params=small_tree(), opt_state=small_tree())
        assert cm._async is False and cm._mgr is not broken
        cm.wait()
        assert cm.all_steps() == [1]

    def test_wait_failure_degrades_to_sync(self, tmp_path):
        from scaletorch_tpu.utils.checkpoint import CheckpointManager

        cm = CheckpointManager(str(tmp_path), async_save=True,
                               retries=1, retry_base_delay=0.01)
        cm._mgr.wait_until_finished = lambda: (_ for _ in ()).throw(
            RuntimeError("pool dead"))
        cm.wait()  # must not raise
        assert cm._async is False

    def test_corrupted_latest_falls_back_to_previous(self, tmp_path):
        cm = self._cm(tmp_path, retries=0)
        t = small_tree()
        for step in (1, 2):
            assert cm.save(step, params={"w": t["w"] * step}, opt_state=t,
                           extra={"tokens_seen": step * 10})
        cm.wait()
        # corrupt step 2: drop the params payload subtree
        import shutil

        victim = next(p for p in (tmp_path / "2").iterdir()
                      if "param" in p.name)
        shutil.rmtree(victim)
        out = cm.load_latest(params=t, opt_state=t)
        assert out is not None and out["step"] == 1
        np.testing.assert_array_equal(out["params"]["w"], t["w"])
        assert out["extra"]["tokens_seen"] == 10
        # the unreadable step must be retired, or orbax's monotonic
        # should_save would silently reject every save in the retrain
        # window (steps <= the stale latest)
        assert cm.all_steps() == [1]
        assert cm.save(2, params=t, opt_state=t)
        cm.wait()
        assert cm.all_steps() == [1, 2]

    def test_all_checkpoints_unreadable_returns_none(self, tmp_path):
        cm = self._cm(tmp_path, retries=0)
        assert cm.load_latest(params=small_tree(),
                              opt_state=small_tree()) is None

    def test_multiprocess_disables_host_local_retry(self, tmp_path):
        # orbax save is a cross-process collective: a host-local retry
        # would re-enter it without peers, so multi-host runs keep the
        # one-attempt, exception-propagating semantics (the flag is set
        # from jax.process_count() at construction; forced here because
        # the test process is single-host)
        inj = FaultInjector(fail_saves=1)
        cm = self._cm(tmp_path, retries=3, fault_injector=inj)
        cm._single_process = False
        with pytest.raises(OSError, match="injected"):
            cm.save(1, params=small_tree(), opt_state=small_tree())


# ---------------------------------------------------------------------------
# End-to-end: inject -> recover through the REAL Trainer.train loop
# ---------------------------------------------------------------------------


class ToyTrainer:
    """The production resilience surface on a mesh-free step.

    Reuses Trainer.train / _rollback_to_last_good / _emergency_checkpoint /
    save_checkpoint / load_checkpoint / checkpoint_manager / _layer_storage
    UNMODIFIED (bound below) — only __init__ and step() differ, replacing
    the 5D SPMD step (which needs newer JAX than the quick tier has) with
    the toy jit model above. The fault paths under test are the real ones.
    """

    def __init__(self, cfg: ScaleTorchTPUArguments, tokens: np.ndarray):
        from scaletorch_tpu.data.dataloader import MicroBatchDataLoader
        from scaletorch_tpu.resilience import ResilienceManager
        from scaletorch_tpu.resilience_distributed import CoordinatedResilience
        from scaletorch_tpu.trainer.metrics import MetricsLogger
        from scaletorch_tpu.trainer.optimizer import create_optimizer
        from scaletorch_tpu.trainer.train_step import make_train_step
        from scaletorch_tpu.utils.logger import get_logger

        self.cfg = cfg
        self.logger = get_logger()
        self.tx, self.schedule = create_optimizer(cfg)
        self.step_fn = make_train_step(
            toy_forward, object(), self.tx, donate=False,
            nonfinite_guard=cfg.nonfinite_guard,
        )
        self.params = toy_params(seed=cfg.seed)
        self.opt_state = self.tx.init(self.params)
        self.resilience = ResilienceManager.from_config(cfg)
        self.coordinator = CoordinatedResilience.from_config(
            cfg, self.resilience)
        self._watchdog = None
        self.loader = MicroBatchDataLoader(
            tokens,
            micro_batch_size=cfg.micro_batch_size,
            gradient_accumulation_steps=cfg.gradient_accumulation_steps,
            seed=cfg.seed,
            read_retries=cfg.data_read_retries,
            retry_base_delay=cfg.data_retry_base_delay,
            max_skipped_batches=cfg.data_max_skipped_batches,
            fault_injector=self.resilience.injector,
        )
        self.metrics = MetricsLogger(
            num_params=V * H * 2, num_layers=1, num_heads=1, head_dim=H,
            seq_len=SEQ, tokens_per_step=self.loader.tokens_per_step,
            log_frequency=cfg.log_frequency, collect_system=False,
        )
        # telemetry: built from the same config the real Trainer uses
        # (disabled unless the test sets telemetry_dir), so the
        # telemetry-aware train loop binds unchanged
        from scaletorch_tpu.telemetry import Telemetry

        self.telemetry = Telemetry.from_config(cfg)
        self._tracer = self.telemetry.tracer
        self.metrics.exporter = self.telemetry.exporter
        self._last_data_fetch_s = 0.0
        self.global_step = 0
        self.tokens_seen = 0
        self.preempted = False
        self.emergency_checkpoint_saved = False
        self._loader_skew = 0
        self._saved_loader_position = None
        self._wandb_logged_step = 0
        self._pp_vpp = 1
        self._train_iter = None
        self._ckpt_mgr = None
        self._wandb = None
        # no ElasticCoordinator by default: the real train() reads
        # self.elastic to decide whether PeerLostError is recoverable
        # (tests/test_elastic.py attaches one for the elastic drills)
        self.elastic = None

    def step(self, batch=None):
        if batch is None:
            if self._train_iter is None:
                self._train_iter = iter(self.loader)
            batch = next(self._train_iter)
        self.params, self.opt_state, m = self.step_fn(
            self.params, self.opt_state, batch
        )
        self.global_step += 1
        self.tokens_seen += int(np.prod(np.shape(batch["input_ids"])))
        return m

    def close(self):
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()
        self.telemetry.close()


def _bind_real_trainer_methods():
    from scaletorch_tpu.trainer.trainer import Trainer

    for name in (
        "train", "save_checkpoint", "load_checkpoint",
        "_rollback_to_last_good", "_emergency_checkpoint", "_layer_storage",
        "_beat", "_stream_position", "_write_crash_report",
        "_watchdog_crash_report", "_watchdog_exit", "_live_snapshot",
        "_agree_all", "_agree_any",
        # elastic continuation (no "_elastic_rebuild_topology": its
        # absence is exactly how the mesh-free toy skips the remesh —
        # _elastic_apply_view getattr-guards it)
        "_elastic_join", "_elastic_recover", "_maybe_elastic_grow",
        "_elastic_apply_view",
    ):
        setattr(ToyTrainer, name, Trainer.__dict__[name])
    ToyTrainer.checkpoint_manager = Trainer.__dict__["checkpoint_manager"]


_bind_real_trainer_methods()


def e2e_cfg(tmp_path=None, **kw):
    defaults = dict(
        micro_batch_size=2, gradient_accumulation_steps=2,
        sequence_length=SEQ, total_train_steps=6, seed=11,
        learning_rate=1e-2, async_checkpointing=False,
        checkpoint_retry_base_delay=0.01, log_frequency=1000,
        sentinel_frequency=1,
    )
    if tmp_path is not None:
        defaults.update(checkpoint_dir=str(tmp_path), save_frequency=2,
                        crash_report_dir=str(tmp_path / "crash_reports"))
    defaults.update(kw)
    return ScaleTorchTPUArguments(**defaults)


def e2e_tokens(n=64):
    return np.random.default_rng(5).integers(
        0, V, size=(n, SEQ + 1)).astype(np.int32)


def params_finite(params):
    return all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(params))


class TestEndToEndFaults:
    def test_injected_nan_skip_policy_keeps_training(self, tmp_path):
        t = ToyTrainer(e2e_cfg(tmp_path, ft_nan_at_step=3,
                               divergence_policy="skip"), e2e_tokens())
        t.train()
        t.close()
        assert t.global_step == 6
        assert params_finite(t.params)
        c = t.resilience.counters()
        assert c["anomalies"] == 1.0 and c["nonfinite_losses"] == 1.0
        assert c["rollbacks"] == 0.0

    def test_injected_nan_rollback_restores_and_fast_forwards(self, tmp_path):
        cfg = e2e_cfg(tmp_path, ft_nan_at_step=3,
                      divergence_policy="rollback")
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()
        t.close()
        # anomaly at step 3 -> restored the step-2 checkpoint, loader
        # fast-forwarded past the bad region, then trained to the target
        assert t.global_step == 6
        assert t.resilience.counters()["rollbacks"] == 1.0
        assert params_finite(t.params)
        # the loader really did fast-forward PAST the bad region: 6
        # optimizer steps consumed 7 stream positions (step 3's batch was
        # retired, not replayed), so the next draw is epoch-0 index 7
        from scaletorch_tpu.data.dataloader import MicroBatchDataLoader

        nxt = next(t._train_iter)
        ref_it = iter(MicroBatchDataLoader(
            e2e_tokens(), micro_batch_size=2,
            gradient_accumulation_steps=2, seed=cfg.seed))
        for _ in range(7):
            expected = next(ref_it)
        expected = next(ref_it)
        np.testing.assert_array_equal(nxt["input_ids"],
                                      expected["input_ids"])

    def test_rollback_skew_survives_checkpoint_restart(self, tmp_path):
        """A restart AFTER a rollback must not replay the retired bad
        batch: the loader skew (stream position ahead of global_step) is
        persisted in every checkpoint and restored on resume."""
        from scaletorch_tpu.data.dataloader import MicroBatchDataLoader

        cfg = e2e_cfg(tmp_path, ft_nan_at_step=3,
                      divergence_policy="rollback")
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()  # rollback at 3 -> skew 1; cadence saves at 4 and 6
        t.close()
        assert t._loader_skew == 1

        t2 = ToyTrainer(e2e_cfg(tmp_path), e2e_tokens())
        assert t2.load_checkpoint()
        assert t2.global_step == 6 and t2._loader_skew == 1
        # next draw continues at stream position 7+1, not 7 — the bad
        # region stays retired across the restart
        t2.step()
        ref_it = iter(MicroBatchDataLoader(
            e2e_tokens(), micro_batch_size=2,
            gradient_accumulation_steps=2, seed=cfg.seed))
        for _ in range(8):
            next(ref_it)
        np.testing.assert_array_equal(
            next(t2._train_iter)["input_ids"],
            next(ref_it)["input_ids"],
        )
        t2.close()

    def test_second_rollback_composes_with_existing_skew(self, tmp_path):
        """A second rollback must fast-forward relative to the TRUE
        stream position (anomaly_step + existing skew), not the raw step
        number — otherwise it rewinds into already-retired data and
        replays the first bad batch."""
        from scaletorch_tpu.data.dataloader import MicroBatchDataLoader

        cfg2 = e2e_cfg(tmp_path, ft_nan_at_step=3,
                       divergence_policy="rollback", total_train_steps=6,
                       max_rollbacks=5)
        t2 = ToyTrainer(cfg2, e2e_tokens())
        t2.train()  # rollback #1: skew 1
        assert t2._loader_skew == 1
        t2.resilience.injector.nan_at_step = t2.global_step + 1
        t2.resilience.injector._nan_fired = False
        t2.train(num_steps=2)  # anomaly on the next step -> rollback #2
        assert t2.resilience.counters()["rollbacks"] == 2.0
        assert t2._loader_skew == 2  # both retired batches stay retired
        # next draw = consumed-position + skew, never a replay
        pos = t2.global_step + t2._loader_skew
        t2.step()  # consumes the draw at `pos`
        ref_it = iter(MicroBatchDataLoader(
            e2e_tokens(), micro_batch_size=2,
            gradient_accumulation_steps=2, seed=cfg2.seed))
        for _ in range(pos + 1):
            next(ref_it)
        np.testing.assert_array_equal(
            next(t2._train_iter)["input_ids"], next(ref_it)["input_ids"])
        t2.close()

    def test_injected_nan_abort_policy_raises(self, tmp_path):
        t = ToyTrainer(e2e_cfg(tmp_path, ft_nan_at_step=3,
                               divergence_policy="abort"), e2e_tokens())
        with pytest.raises(TrainingDivergedError):
            t.train()
        t.close()

    def test_sigterm_emergency_checkpoint_then_resume_auto_matches(
            self, tmp_path):
        tokens = e2e_tokens()
        # ground truth: uninterrupted 6-step run (no checkpoint cadence
        # interference — save_frequency stays on to match the recovery run)
        ref_dir = tmp_path / "ref"
        t_ref = ToyTrainer(e2e_cfg(ref_dir), tokens)
        t_ref.train()
        t_ref.close()
        ref = jax.device_get(t_ref.params)
        assert not t_ref.preempted

        # preempted run: simulated SIGTERM after step 3 -> emergency
        # checkpoint at the next step boundary + clean early return
        run_dir = tmp_path / "run"
        t1 = ToyTrainer(e2e_cfg(run_dir, ft_sigterm_at_step=3), tokens)
        t1.train()
        t1.close()
        assert t1.preempted
        assert t1.global_step == 3
        assert t1.checkpoint_manager.latest_step() == 3

        # restarted job: --resume auto semantics (train.py), same target
        t2 = ToyTrainer(e2e_cfg(run_dir), tokens)
        assert t2.load_checkpoint()
        assert t2.global_step == 3
        t2.train()  # default target is ABSOLUTE total_train_steps
        t2.close()
        assert t2.global_step == 6
        final = jax.device_get(t2.params)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(final)):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_preemption_right_after_rollback_persists_skew(self, tmp_path):
        """Preemption at the same step a rollback restored to: the
        on-disk checkpoint has the PRE-rollback loader position, so the
        emergency path must replace it (orbax silently skips same-step
        saves) — otherwise the restart replays the diverged batch."""
        cfg = e2e_cfg(tmp_path, ft_nan_at_step=3, ft_sigterm_at_step=3,
                      divergence_policy="rollback")
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()
        assert t.preempted and t.global_step == 2
        assert t._loader_skew == 1 and t.emergency_checkpoint_saved
        t.close()

        t2 = ToyTrainer(e2e_cfg(tmp_path), e2e_tokens())
        assert t2.load_checkpoint()
        # the replacement checkpoint carries the post-rollback position:
        # the bad batch stays retired across the restart
        assert t2.global_step == 2 and t2._loader_skew == 1
        t2.close()

    def test_sigterm_without_checkpoint_dir_still_exits_cleanly(self):
        t = ToyTrainer(e2e_cfg(None, ft_sigterm_at_step=2), e2e_tokens())
        t.train()
        t.close()
        assert t.preempted and t.global_step == 2

    def test_first_n_save_failures_retried_without_data_loss(self, tmp_path):
        cfg = e2e_cfg(tmp_path, ft_fail_saves=2, checkpoint_retries=3)
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()
        t.close()
        assert t.global_step == 6
        # both cadence saves landed despite the injected failures
        assert t.checkpoint_manager.all_steps() == [2, 4, 6]
        # and the newest checkpoint resumes cleanly
        t2 = ToyTrainer(e2e_cfg(tmp_path), e2e_tokens())
        assert t2.load_checkpoint()
        assert t2.global_step == 6 and t2.tokens_seen == t.tokens_seen
        t2.close()

    def test_save_failures_beyond_retries_never_kill_the_run(self, tmp_path):
        cfg = e2e_cfg(tmp_path, ft_fail_saves=100, checkpoint_retries=1)
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()
        t.close()
        assert t.global_step == 6
        assert params_finite(t.params)

    def test_corrupt_shard_skipped_and_retired_across_restart(self, tmp_path):
        """An unreadable stream region (ft_bad_batch_at_step) is skipped
        after retries, the skip is absorbed into loader_position, and a
        restarted run keeps the region retired (no replay, no
        double-count)."""
        cfg = e2e_cfg(tmp_path, ft_bad_batch_at_step=2,
                      data_read_retries=1, data_retry_base_delay=0.001)
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()
        t.close()
        assert t.global_step == 6
        # 6 optimizer steps consumed 7 stream positions (slot 2 skipped)
        assert t.loader.position == 7
        assert t.loader.skipped_positions == [2]
        assert t._loader_skew == 1

        t2 = ToyTrainer(e2e_cfg(tmp_path), e2e_tokens())
        assert t2.load_checkpoint()
        assert t2.global_step == 6 and t2._loader_skew == 1
        t2.step()
        from scaletorch_tpu.data.dataloader import MicroBatchDataLoader

        ref_it = iter(MicroBatchDataLoader(
            e2e_tokens(), micro_batch_size=2,
            gradient_accumulation_steps=2, seed=cfg.seed))
        for _ in range(8):
            next(ref_it)
        np.testing.assert_array_equal(
            next(t2._train_iter)["input_ids"], next(ref_it)["input_ids"])
        t2.close()


# ---------------------------------------------------------------------------
# Layer-storage validation (satellite: quick coverage of the error path)
# ---------------------------------------------------------------------------


class TestLayerStorageValidation:
    def test_mismatch_raises_with_remedy(self):
        from scaletorch_tpu.trainer.trainer import validate_layer_storage

        with pytest.raises(ValueError, match="convert_layer_storage"):
            validate_layer_storage(
                "model_order", "interleaved_pp2_vpp2",
                pp_engine="interleaved", pp_virtual_stages=2,
            )

    def test_match_passes(self):
        from scaletorch_tpu.trainer.trainer import validate_layer_storage

        validate_layer_storage(
            "interleaved_pp2_vpp2", "interleaved_pp2_vpp2",
            pp_engine="interleaved", pp_virtual_stages=2,
        )


# ---------------------------------------------------------------------------
# Config surface
# ---------------------------------------------------------------------------


class TestResilienceConfig:
    def test_resume_choices_validated(self):
        with pytest.raises(ValueError, match="resume"):
            ScaleTorchTPUArguments(resume="maybe")

    def test_resume_from_checkpoint_aliases_auto(self):
        cfg = ScaleTorchTPUArguments(resume_from_checkpoint=True)
        assert cfg.resume == "auto"

    def test_explicit_must_not_weakened_by_alias(self):
        cfg = ScaleTorchTPUArguments(resume_from_checkpoint=True,
                                     resume="must", checkpoint_dir="/ckpt")
        assert cfg.resume == "must"

    def test_resume_must_requires_checkpoint_dir(self):
        with pytest.raises(ValueError, match="checkpoint_dir"):
            ScaleTorchTPUArguments(resume="must")

    def test_divergence_policy_validated(self):
        with pytest.raises(ValueError, match="divergence_policy"):
            ScaleTorchTPUArguments(divergence_policy="panic")

    def test_negative_knobs_rejected(self):
        with pytest.raises(ValueError, match="ft_fail_saves"):
            ScaleTorchTPUArguments(ft_fail_saves=-1)
        with pytest.raises(ValueError, match="checkpoint_retries"):
            ScaleTorchTPUArguments(checkpoint_retries=-1)

    def test_spike_factor_at_or_below_one_rejected(self):
        # (0, 1] would flag nearly every healthy step as a spike
        with pytest.raises(ValueError, match="loss_spike_factor"):
            ScaleTorchTPUArguments(loss_spike_factor=0.5)
        with pytest.raises(ValueError, match="loss_spike_factor"):
            ScaleTorchTPUArguments(loss_spike_factor=-2.0)
        ScaleTorchTPUArguments(loss_spike_factor=2.0)  # valid
        ScaleTorchTPUArguments(loss_spike_factor=0.0)  # off

    def test_ema_beta_out_of_range_rejected(self):
        with pytest.raises(ValueError, match="loss_ema_beta"):
            ScaleTorchTPUArguments(loss_ema_beta=1.5)
        with pytest.raises(ValueError, match="loss_ema_beta"):
            ScaleTorchTPUArguments(loss_ema_beta=-0.1)
