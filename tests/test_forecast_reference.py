"""The forecaster against its plain reference (benchmarks/
forecast_reference.py: float32 throughout, matmuls at the highest
precision), at a small size and at the widths the service runs, through the
comparison the chip runs too
(scripts/forecast_vs_reference.py, which states the tolerances and their
reasons); the reference's own draws from the seed against the program's; the
books and the boot compile of the service (models/service.py); and what the
admin API says of it.
"""

import asyncio
import json
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chanamq_tpu import device  # noqa: E402
from chanamq_tpu.broker.broker import Broker  # noqa: E402
from chanamq_tpu.broker.server import BrokerServer  # noqa: E402
from chanamq_tpu.models.forecaster import (  # noqa: E402
    ForecasterConfig, init_params,
)
from chanamq_tpu.models.service import ForecastService  # noqa: E402
from chanamq_tpu.models.telemetry import training_batch  # noqa: E402
from chanamq_tpu.rest.admin import AdminServer  # noqa: E402
from chanamq_tpu.store.memory import MemoryStore  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "scripts"))
import forecast_vs_reference as cmp  # noqa: E402

ref = cmp.ref

pytestmark = pytest.mark.asyncio

FORECAST_REL = cmp.TOLERANCES["forecast_rel"]
LOSS_REL = cmp.TOLERANCES["loss_rel"]
STEP_REL = cmp.TOLERANCES["step_rel"]
ROUND_STD = cmp.TOLERANCES["round_std"]

# dims and batch: a small model, and ForecastService's defaults (the cell's)
SIZES = {
    "small": {"d_model": 32, "n_heads": 4, "d_ff": 64, "n_layers": 1,
              "n_features": 8, "seq_len": 8, "batch": 8},
    "service": {"d_model": 64, "n_heads": 4, "d_ff": 256, "n_layers": 2,
                "n_features": 8, "seq_len": 64, "batch": 16},
}


def dims_of(size: dict) -> dict:
    return {k: v for k, v in size.items() if k != "batch"}


def forward_error(size: dict, seed: int, dtype=None) -> float:
    x, _ = cmp.normal_batch(dims_of(size), size["batch"], seed)
    return cmp.forward_error(dims_of(size), x, seed, dtype)


def step_errors(size: dict, seed: int, dtype=None) -> dict:
    """One train step from seeded params on a seeded batch."""
    x, y = cmp.normal_batch(dims_of(size), size["batch"], seed)
    return cmp.step_errors(dims_of(size), x, y, seed, dtype)


def telemetry_ring(n: int, seed: int) -> np.ndarray:
    """A seeded stand-in for a broker's ring: rates that swing with load,
    gauges that follow them, a constant consumer count."""
    rng = np.random.default_rng(seed)
    t = np.arange(n, dtype=np.float64)
    load = 40_000 * (1 + 0.3 * np.sin(t / 7.0)) + rng.normal(0, 800, n)
    ring = np.stack([
        load, 0.64 * load + rng.normal(0, 500, n),
        np.abs(rng.normal(300, 80, n)), np.zeros(n), np.full(n, 2.0),
        12 * load, 12 * 0.64 * load + rng.normal(0, 6_000, n),
        load + rng.normal(0, 300, n)], axis=1)
    return ring.astype(np.float32)


def round_service(size: dict) -> ForecastService:
    return cmp.round_service(dims_of(size), size["batch"])


# -- the mathematics ----------------------------------------------------------


@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("seed", [0, 1])
def test_the_jitted_forward_agrees_with_the_reference(size, seed):
    assert forward_error(SIZES[size], seed) <= FORECAST_REL


@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("seed", [0, 1])
def test_one_train_step_agrees_with_the_reference(size, seed):
    errors = step_errors(SIZES[size], seed)
    assert errors["loss_rel"] <= LOSS_REL, errors
    assert errors["step_rel"] <= STEP_REL, errors


@pytest.mark.parametrize("size", list(SIZES))
def test_a_round_of_the_service_agrees_with_the_reference(size):
    service = round_service(SIZES[size])
    history = telemetry_ring(400, seed=7)
    assert cmp.round_error(service, history) <= ROUND_STD
    assert service.broker.metrics.forecast_rounds == 1


@pytest.mark.parametrize("dtype", [jnp.float8_e4m3fn, jnp.float8_e5m2])
@pytest.mark.parametrize("size", list(SIZES))
def test_float8_matmul_inputs_fail_the_tolerances(size, dtype):
    """The same program with float8 where it runs bfloat16: the tolerances
    are tight enough to tell."""
    fails = [forward_error(SIZES[size], 0, dtype) > FORECAST_REL]
    errors = step_errors(SIZES[size], 0, dtype)
    fails += [errors["loss_rel"] > LOSS_REL, errors["step_rel"] > STEP_REL]
    assert any(fails), errors
    assert errors["step_rel"] > STEP_REL, errors  # the update, on its own


@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("seed", [0, 7])
def test_the_reference_draws_the_programs_params_from_the_seed(size, seed):
    dims = dims_of(SIZES[size])
    want = init_params(jax.random.PRNGKey(seed), ForecasterConfig(**dims))
    got = ref.init_params(seed, dims)
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(got[name], np.asarray(want[name]),
                                      err_msg=name)


def test_the_reference_draws_the_services_batch_from_the_seed():
    history = telemetry_ring(300, seed=3)
    normed, mean, std = ref.normalize(history)
    x, y = ref.training_batch(normed, 64, 16, seed=0)
    want_x, want_y = training_batch(normed, 64, 16, np.random.default_rng(0))
    np.testing.assert_array_equal(x, want_x)
    np.testing.assert_array_equal(y, want_y)
    assert np.allclose(normed * std + mean, history, rtol=1e-5, atol=1e-2)


def test_the_comparison_script_holds_the_program_and_fails_float8(tmp_path):
    """The script the chip runs, end to end at the small widths over the
    series recorded on the chip: the program within every tolerance, each
    float8 variant outside one."""
    size = SIZES["small"]
    config = tmp_path / "config.json"
    config.write_text(json.dumps({"model": dict(
        dims_of(size), batch=size["batch"], steps_per_round=20, lr=1e-3)}))
    out = tmp_path / "compare.json"
    assert cmp.main(["--config", str(config), "--out", str(out)]) == 0
    result = json.loads(out.read_text())
    assert result["ok"] and result["ticks"] == 137
    assert result["bfloat16_outside"] == []
    assert result["float8_e4m3fn_outside"] and result["float8_e5m2_outside"]


def test_the_flop_count_of_a_step():
    """At the service's widths: 0.236 GFLOP a forward, 0.708 a step; the
    embedding and the readout alone counted by hand."""
    dims = dims_of(SIZES["service"])
    assert ref.forward_flops(dims, 16) == 235_945_984
    assert ref.train_step_flops(dims, 16) == 3 * 235_945_984
    one = dict(dims, n_layers=0)  # embedding and readout only
    assert ref.forward_flops(one, 2) == 2 * 2 * 64 * 8 * 64 + 2 * 2 * 64 * 8


# -- the service's boot compile, books and accuracy ---------------------------


def compile_events() -> int:
    held = device.claimed()
    return held.cache_hits + held.cache_misses


async def test_start_compiles_so_the_first_round_compiles_nothing():
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await server.start()
    service = ForecastService(
        server.broker, interval_s=3600.0, seq_len=8, batch=8,
        model_kwargs={"d_model": 32, "n_heads": 4, "d_ff": 64, "n_layers": 1})
    await service.start()
    try:
        state = service._jax_state
        assert state is not None  # built and compiled before start returned
        sizes = state["step"]._cache_size(), state["forward"]._cache_size()
        assert sizes == (1, 1)
        events = compile_events()
        loop = asyncio.get_running_loop()
        steps, loss, forecast = await loop.run_in_executor(
            service._executor, service._round, telemetry_ring(100, seed=3))
        assert steps == service.steps_per_round and forecast is not None
        assert compile_events() == events
        assert (state["step"]._cache_size(),
                state["forward"]._cache_size()) == sizes
        # the names the device trace shows
        text = state["step"].lower(
            state["params"], state["momentum"],
            (np.zeros((8, 8, 8), np.float32),
             np.zeros((8, 8), np.float32))).as_text()
        assert "jit_forecast_train_step" in text
        assert state["forward"].__name__ == "forecast_predict"
    finally:
        await service.stop()
        await server.stop()


async def test_the_books_count_ticks_and_a_round():
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await server.start()
    metrics = server.broker.metrics
    service = ForecastService(
        server.broker, interval_s=0.01, train_interval_s=3600.0, seq_len=8,
        batch=8, steps_per_round=3,
        model_kwargs={"d_model": 32, "n_heads": 4, "d_ff": 64, "n_layers": 1})
    await service.start()
    try:
        # no round compiled or ran at boot: start() dropped its warm-up
        assert (metrics.forecast_rounds, metrics.forecast_train_steps,
                metrics.forecast_predicts) == (0, 0, 0)
        deadline = asyncio.get_running_loop().time() + 30
        while service.ring.count < 12:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.01)
        # one tick, one push: the two counts move together
        assert metrics.forecast_samples == service.ring.count
        assert metrics.forecast_sample_ns > 0
        before = metrics.snapshot()
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(service._executor, service._round,
                                   telemetry_ring(50, seed=5))
        after = metrics.snapshot()
        moved = {k: after[k] - before[k] for k in (
            "forecast_rounds", "forecast_train_steps", "forecast_predicts")}
        assert moved == {"forecast_rounds": 1, "forecast_train_steps": 3,
                         "forecast_predicts": 1}
        for name in ("round", "train", "predict"):
            assert after[f"forecast_{name}_ns"] > before[f"forecast_{name}_ns"]
        parts = sum(after[f"forecast_{n}_ns"] - before[f"forecast_{n}_ns"]
                    for n in ("train", "predict"))
        assert after["forecast_round_ns"] - before["forecast_round_ns"] >= parts
    finally:
        await service.stop()
        await server.stop()


async def test_persistence_is_scored_beside_the_forecast():
    broker = Broker(store=MemoryStore(), message_sweep_interval_s=3600.0)
    service = ForecastService(broker)
    n = service.n_features
    for forecast, base, realized in ((10.0, 12.0, 13.0), (10.0, 5.0, 9.0)):
        service._pending_forecast = np.full(n, forecast, dtype=np.float32)
        service._pending_base = np.full(n, base, dtype=np.float32)
        service.score_tick(np.full(n, realized, dtype=np.float32))
    accuracy = service.accuracy()
    name = service.feature_names[0]
    assert accuracy["scored"] == 2
    assert accuracy["mae"][name] == pytest.approx((3 + 1) / 2)
    assert accuracy["persistence_mae"][name] == pytest.approx((1 + 4) / 2)


# -- what the admin API says --------------------------------------------------


async def _get(port: int, path: str) -> dict:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(-1), 10)
    writer.close()
    return json.loads(raw.partition(b"\r\n\r\n")[2])


async def test_the_overview_names_the_forecaster_when_it_is_on():
    """The admin API says whether the forecaster runs (/admin/forecast),
    and /admin/overview carries its books either way: zeros while off."""
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await server.start()
    admin = AdminServer(server.broker, port=0)
    await admin.start()
    service = None
    try:
        assert (await _get(admin.bound_port, "/admin/forecast")) == {
            "enabled": False}
        books = (await _get(admin.bound_port, "/admin/overview"))["metrics"]
        assert {k: v for k, v in books.items()
                if k.startswith("forecast_")} == {
            f"forecast_{name}": 0 for name in (
                "samples", "sample_ns", "rounds", "round_ns", "train_steps",
                "train_ns", "predicts", "predict_ns")}
        service = ForecastService(
            server.broker, interval_s=3600.0, seq_len=8, batch=8,
            model_kwargs={"d_model": 32, "n_heads": 4, "d_ff": 64,
                          "n_layers": 1})
        await service.start()
        doc = await _get(admin.bound_port, "/admin/forecast")
        assert doc["enabled"] and doc["window"] == 8 and doc["rounds"] == 0
        doc = await _get(admin.bound_port, "/admin/overview")
        assert "forecast" not in doc
        assert doc["metrics"]["forecast_rounds"] == 0
    finally:
        if service is not None:
            await service.stop()
        await admin.stop()
        await server.stop()
