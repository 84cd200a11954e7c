"""Forecast service: live telemetry -> off-path JAX train/predict -> admin.

Closes the loop models/forecaster.py:1-16 promises (SURVEY.md §7.1's one
honest JAX role — batch analytics over broker metrics, never on the message
path):

- a sampler task on the broker's event loop appends one telemetry vector
  per tick to a TelemetryRing (models/telemetry.py) — numpy only, O(#queues)
  per tick, no JAX on the loop;
- every train-interval, a single worker thread (run_in_executor) takes a
  copy of the ring, z-scores it, runs a few train steps of the causal
  transformer on sampled (window -> next-vector) pairs, then forwards the
  newest window to produce the next-tick forecast — denormalized back to
  real units. The event loop never blocks: JAX compilation and execution
  happen entirely on the worker thread, and at most one round is in
  flight;
- the latest forecast is served by the admin API at GET /admin/forecast
  and as chanamq_forecast_* Prometheus gauges (rest/admin.py).

start() builds the JAX state and compiles the train step and the forward
on the worker thread before it returns, so no round compiles while the
broker serves. The work is on the books always (Metrics: forecast_samples,
forecast_rounds, forecast_train_steps, forecast_predicts and their wall
in ns, two clock reads a site) and, under a profiler session, in three
flat spans: forecast.sample on the loop, forecast.train and
forecast.predict on the worker thread.

Enable with chana.mq.forecast.enabled (off by default: a broker should not
spin an accelerator workload unless the operator asks for capacity
forecasting).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import time
from typing import TYPE_CHECKING, Any, Optional

import numpy as np

from .. import device
from .telemetry import (
    FEATURES, TelemetryRing, TopKSlots, counter_state, normalization,
    sample, training_batch,
)

if TYPE_CHECKING:  # pragma: no cover
    from ..broker.broker import Broker

log = logging.getLogger("chanamq.forecast")


class ForecastService:
    """Samples broker telemetry and maintains a next-tick forecast."""

    def __init__(
        self,
        broker: "Broker",
        *,
        interval_s: float = 1.0,
        train_interval_s: float = 30.0,
        seq_len: int = 64,
        history: int = 4096,
        batch: int = 16,
        steps_per_round: int = 20,
        lr: float = 1e-3,
        queue_top_k: int = 0,
        model_kwargs: Optional[dict[str, Any]] = None,
    ) -> None:
        self.broker = broker
        self.interval_s = interval_s
        self.train_interval_s = train_interval_s
        self.seq_len = seq_len
        self.batch = batch
        # per-queue awareness: widen each sample with (depth, publish_rate)
        # of the K busiest queues from the per-entity telemetry rings
        # (broker.telemetry). Slot columns are PINNED to queue identity
        # (TopKSlots): a slot keeps tracking the same queue while it stays
        # in the top-K set, with explicit eviction + a one-tick zero reset
        # on reassignment, so a training window never splices two queues'
        # series into one column. Zeros when telemetry is off.
        self.queue_top_k = queue_top_k
        self.topk = TopKSlots(queue_top_k)
        self.feature_names: tuple[str, ...] = FEATURES + tuple(
            name
            for i in range(queue_top_k)
            for name in (f"top{i}_depth", f"top{i}_publish_rate"))
        self.n_features = len(self.feature_names)
        self.steps_per_round = steps_per_round
        self.lr = lr
        # compact model by default: 8 features need nowhere near the
        # flagship dims, and the worker thread shares cores with the broker
        self.model_kwargs = dict(model_kwargs or {})
        self.model_kwargs.setdefault("d_model", 64)
        self.model_kwargs.setdefault("n_heads", 4)
        self.model_kwargs.setdefault("d_ff", 256)
        self.model_kwargs.setdefault("n_layers", 2)
        if history < seq_len + 1:
            # the train gate needs seq_len+1 retained vectors; a smaller
            # ring would silently never train
            raise ValueError(
                f"forecast history ({history}) must exceed window "
                f"({seq_len}) — the ring must hold window+1 vectors")
        self.ring = TelemetryRing(history, width=self.n_features)
        self._task: Optional[asyncio.Task] = None
        # one worker: params live on this thread, rounds never overlap
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="chanamq-forecast")
        self._round_inflight = False
        self._stopping = False  # cooperative cancel for an in-flight round
        self._np_rng = np.random.default_rng(0)
        # the process's device, claimed in start(); the JAX state on it
        # is built on the worker thread, in start() and after a divergence
        self.device: Optional[device.Device] = None
        self._jax_state: Optional[dict[str, Any]] = None
        # latest results (event loop writes, anyone reads)
        self.forecast: Optional[dict[str, float]] = None
        self.loss: Optional[float] = None
        self.trained_steps = 0
        self.rounds = 0
        self.updated_at: Optional[float] = None
        self.last_error: Optional[str] = None
        # forecast accuracy: each realized tick is scored against the
        # forecast that predicted it (per-feature absolute error; running
        # MAE). The control plane gates actuation on this, and operators
        # see it at GET /admin/forecast + chanamq_forecast_error_* gauges.
        self._pending_forecast: Optional[np.ndarray] = None
        self.error_scored = 0
        self.error_last: Optional[np.ndarray] = None
        self.error_mae: Optional[np.ndarray] = None
        # the persistence forecast (next tick = the last tick the round
        # saw) scored over the same ticks: what the model has to beat
        self._round_base: Optional[np.ndarray] = None
        self._pending_base: Optional[np.ndarray] = None
        self.persistence_scored = 0
        self.persistence_mae: Optional[np.ndarray] = None

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        # the worker thread jits on whatever device this process holds:
        # claim it now, at boot, so a wrong backend fails the boot
        self.device = device.claim()
        # and compiles there now, before the broker serves, not at the
        # first round
        await asyncio.get_running_loop().run_in_executor(
            self._executor, self._warm)
        self.broker.forecaster = self
        self._task = asyncio.get_event_loop().create_task(self._run())
        self._task.add_done_callback(self._on_run_done)
        log.info(
            "forecast service on: interval=%.3gs train-interval=%.3gs "
            "window=%d model=%s device=%s (%s)", self.interval_s,
            self.train_interval_s, self.seq_len, self.model_kwargs,
            self.device.platform, self.device.kind)

    async def stop(self) -> None:
        # cooperative cancel: concurrent.futures joins worker threads at
        # interpreter exit regardless of shutdown(wait=False), so an
        # in-flight round must notice and bail between train steps
        self._stopping = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        self._executor.shutdown(wait=False, cancel_futures=True)
        if getattr(self.broker, "forecaster", None) is self:
            self.broker.forecaster = None

    # -- sampling loop (event loop; numpy only) ----------------------------

    async def _run(self) -> None:
        counters = counter_state(self.broker)
        metrics = self.broker.metrics
        last = time.monotonic()
        next_train = last + self.train_interval_s
        while True:
            await asyncio.sleep(self.interval_s)
            t0 = time.perf_counter_ns()
            try:
                with device.span("forecast.sample"):
                    now = time.monotonic()
                    vec, counters = sample(self.broker, counters, now - last)
                    last = now
                    if self.queue_top_k:
                        telemetry = getattr(self.broker, "telemetry", None)
                        extra = (
                            self.topk.update(
                                *telemetry.queues.latest_matrix())
                            if telemetry is not None
                            else np.zeros(2 * self.queue_top_k,
                                          dtype=np.float32))
                        vec = np.concatenate([vec, extra])
                    self.score_tick(vec)
                    self.ring.push(vec)
                    if (now >= next_train and not self._round_inflight
                            and len(self.ring) >= self.seq_len + 1):
                        next_train = now + self.train_interval_s
                        self._round_inflight = True
                        # copy: the worker never sees the ring
                        history = self.ring.history()
                        self._round_base = history[-1]
                        loop = asyncio.get_event_loop()
                        loop.run_in_executor(
                            self._executor, self._round, history
                        ).add_done_callback(self._on_round_done)
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001 — a bad sample tick
                # must not kill forecasting forever; record and keep sampling
                self.last_error = repr(exc)
                log.exception("forecast sample tick failed")
            finally:
                metrics.forecast_samples += 1
                metrics.forecast_sample_ns += time.perf_counter_ns() - t0

    def _on_run_done(self, task: "asyncio.Task") -> None:
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            self.last_error = repr(exc)
            log.error("forecast sampler task died", exc_info=exc)

    def _on_round_done(self, fut: "asyncio.Future") -> None:
        self._round_inflight = False
        try:
            result = fut.result()
        except Exception as exc:  # noqa: BLE001 — survives a bad round
            self.last_error = repr(exc)
            log.exception("forecast round failed")
            return
        steps, loss, forecast = result
        self.trained_steps += steps
        if forecast is None:
            return  # round bailed early (service stopping)
        self.rounds += 1
        self.loss = loss
        self.forecast = forecast
        self.updated_at = time.time()
        self.last_error = None
        # the next realized tick scores this forecast (score_tick), and
        # the last tick the round saw as the persistence forecast
        self._pending_forecast = np.array(
            [forecast[name] for name in self.feature_names],
            dtype=np.float32)
        self._pending_base = self._round_base

    # -- forecast accuracy (event loop; numpy only) ------------------------

    def score_tick(self, vec: np.ndarray) -> None:
        """Score the pending next-tick forecast against the realized
        vector: per-feature absolute error, folded into a running MAE; the
        persistence forecast (the last tick the round saw) beside it over
        the same ticks. A forecast is consumed by the first tick that
        follows it."""
        pending, base = self._pending_forecast, self._pending_base
        if pending is None or len(pending) != len(vec):
            return
        self._pending_forecast = self._pending_base = None
        vec = np.asarray(vec, dtype=np.float32)
        err = np.abs(vec - pending)
        self.error_last = err
        self.error_scored += 1
        if self.error_mae is None:
            self.error_mae = err.copy()
        else:
            self.error_mae += (err - self.error_mae) / self.error_scored
        if base is not None and len(base) == len(vec):
            naive = np.abs(vec - base)
            self.persistence_scored += 1
            if self.persistence_mae is None:
                self.persistence_mae = naive
            else:
                self.persistence_mae += (
                    naive - self.persistence_mae) / self.persistence_scored
        # NaN/inf can only come from a poisoned forecast; drop the stats
        # rather than serving non-finite gauges
        if not np.isfinite(err).all():
            self.error_last = None
            self.error_mae = None
            self.persistence_mae = None
            self.error_scored = self.persistence_scored = 0

    def accuracy(self) -> Optional[dict[str, Any]]:
        if not self.error_scored or self.error_mae is None:
            return None
        return {
            "scored": self.error_scored,
            "mae": {name: float(v) for name, v in
                    zip(self.feature_names, self.error_mae)},
            "persistence_mae": (
                {name: float(v) for name, v in
                 zip(self.feature_names, self.persistence_mae)}
                if self.persistence_mae is not None else None),
            "last_abs_error": (
                {name: float(v) for name, v in
                 zip(self.feature_names, self.error_last)}
                if self.error_last is not None else None),
        }

    def slot_queues(self) -> list:
        """Queue identity pinned to each top-K feature slot (None=free);
        lets the control plane map top{i}_* forecasts back to queues."""
        return self.topk.slot_queues()

    # -- train/predict round (worker thread; owns all JAX state) -----------

    def _jax_setup(self) -> dict[str, Any]:
        import jax

        from .forecaster import (
            ForecasterConfig, forward, init_momentum, init_params,
            make_train_step,
        )

        cfg = ForecasterConfig(
            n_features=self.n_features, seq_len=self.seq_len,
            **self.model_kwargs)
        train_step = make_train_step(cfg, lr=self.lr)

        # functions with names, not lambdas: the device trace's modules
        # read jit_forecast_train_step and jit_forecast_predict, apart
        # from the router's jit_topic_match
        def forecast_train_step(params, momentum, batch):
            return train_step(params, momentum, batch)

        def forecast_predict(params, window):
            return forward(params, window, cfg)

        params = init_params(jax.random.PRNGKey(0), cfg)
        state = {
            "cfg": cfg,
            "params": params,
            "momentum": init_momentum(params),
            "step": jax.jit(forecast_train_step),
            "forward": jax.jit(forecast_predict),
        }
        return state

    def _warm(self) -> None:
        """Build the JAX state and run the train step and the forward once
        on zero inputs of a round's shapes, dropping what they return: both
        are compiled (or read from the compile cache) before the broker
        serves, so no round compiles."""
        state = self._jax_setup()
        x = np.zeros((self.batch, self.seq_len, self.n_features),
                     dtype=np.float32)
        y = np.zeros((self.batch, self.n_features), dtype=np.float32)
        float(state["step"](state["params"], state["momentum"], (x, y))[2])
        np.asarray(state["forward"](state["params"], x[:1]))
        self._jax_state = state

    def _round(
        self, history: np.ndarray
    ) -> tuple[int, Optional[float], Optional[dict[str, float]]]:
        """One off-path round: K train steps + next-tick forecast."""
        metrics = self.broker.metrics
        t0 = time.perf_counter_ns()
        try:
            return self._train_and_predict(history, metrics)
        finally:
            metrics.forecast_rounds += 1
            metrics.forecast_round_ns += time.perf_counter_ns() - t0

    def _train_and_predict(
        self, history: np.ndarray, metrics
    ) -> tuple[int, Optional[float], Optional[dict[str, float]]]:
        if self._jax_state is None:  # rebuilt after a divergence
            self._jax_state = self._jax_setup()
        state = self._jax_state
        mean, std = normalization(history)
        normed = (history - mean) / std
        pairs = training_batch(normed, self.seq_len, self.batch, self._np_rng)
        steps = 0
        loss = None
        if pairs is not None and self.steps_per_round:
            # from the first step's dispatch to the loss on the host: the
            # steps run back to back on the device
            t0 = time.perf_counter_ns()
            with device.span("forecast.train"):
                for _ in range(self.steps_per_round):
                    if self._stopping:
                        break
                    state["params"], state["momentum"], loss_arr = \
                        state["step"](state["params"], state["momentum"],
                                      pairs)
                    steps += 1
                if steps and not self._stopping:
                    loss = float(loss_arr)
            metrics.forecast_train_steps += steps
            metrics.forecast_train_ns += time.perf_counter_ns() - t0
        if self._stopping:
            return steps, loss, None
        window = normed[-self.seq_len:][None, ...].astype(np.float32)
        t0 = time.perf_counter_ns()
        with device.span("forecast.predict"):
            pred = np.asarray(state["forward"](state["params"], window))[0]
        metrics.forecast_predicts += 1
        metrics.forecast_predict_ns += time.perf_counter_ns() - t0
        if (loss is not None and not np.isfinite(loss)) \
                or not np.isfinite(pred).all():
            # diverged despite clipping: drop the poisoned params and start
            # clean next round rather than serving NaN gauges
            self._jax_state = None
            raise RuntimeError(
                f"forecaster diverged (loss={loss}); reinitializing")
        real = pred * std + mean
        # rates/gauges cannot be negative; the model can briefly overshoot
        real = np.maximum(real, 0.0)
        forecast = {name: float(v)
                    for name, v in zip(self.feature_names, real)}
        return steps, loss, forecast

    # -- introspection (admin API) -----------------------------------------

    def snapshot(self) -> dict[str, Any]:
        observed = self.ring.latest()
        return {
            "enabled": True,
            "samples": self.ring.count,
            "interval_s": self.interval_s,
            "window": self.seq_len,
            "rounds": self.rounds,
            "trained_steps": self.trained_steps,
            "loss": self.loss,
            "queue_top_k": self.queue_top_k,
            "observed": (
                {name: float(v)
                 for name, v in zip(self.feature_names, observed)}
                if observed is not None else None),
            "forecast": self.forecast,
            "accuracy": self.accuracy(),
            "slot_queues": [
                list(key) if key is not None else None
                for key in self.topk.slot_queues()],
            "updated_at": self.updated_at,
            "error": self.last_error,
        }
