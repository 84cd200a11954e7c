"""The broker's forecaster against its plain reference
(benchmarks/forecast_reference.py), at the widths a benchmark configuration
states in its `model` block:

    python scripts/forecast_vs_reference.py \
        [--config benchmarks/configs/topic-telemetry-forecast.json] \
        [--series scripts/data/forecast_series_tpu_v5e.csv] [--out FILE]

Both sides draw from the same seeds on their own: the program its
parameters with `models/forecaster.init_params` and its batch with
`models/telemetry.training_batch`, the reference with its `init_params` and
`training_batch`. Compared, for the program as it runs (bfloat16 matmul
inputs) and for the same code with float8 inputs:

- a forward and one train step on seeded standard-normal batches (seeds 0
  and 1) and on a batch drawn from the telemetry series;
- a whole round of the service (`ForecastService._round`) over the series
  against `forecast_reference.round_forecast`.

The series is a CSV of `/admin/forecast` `observed` vectors, one row a
tick, headed by the feature names. The one in scripts/data was recorded on
a TPU v5e from a run of the cell `topic_forecast_fleet_keys`: its admin API
polled every 50 ms, each new sample kept (137 ticks at 100 ms).

Prints one JSON line, also written to --out; exits 0 when the program is
within every tolerance on every input and each float8 variant is outside at
least one, else 1.

The tolerances, each with its reason. The program rounds every matmul's
inputs and its residual stream to bfloat16 and accumulates in float32; the
same code with float8 inputs must fail at least one of them:

- forecast_rel 0.03: max |program - reference| over a forward's outputs, as
  a share of the reference's largest |output|. bfloat16 keeps 8 bits of
  mantissa (relative rounding 2**-9 = 0.002 an operation); through two
  layers the outputs read 0.004-0.012 off; float8 e4m3 (3 bits) reads
  0.057-0.146.
- loss_rel 0.02: |program - reference| / reference of one step's loss, a
  mean of squares: bfloat16 0.0000-0.0013 on seeded normal batches, 0.0067
  on a batch of a ring recorded from a broker under load; float8 0.075-0.087
  there, 0.0035-0.042 on the normal batches. A mean of squares averages the
  rounding out, so this one alone does not tell the two apart on every
  batch: the step's own error does.
- step_rel 0.04: ||params after one program step - after one reference
  step|| over ||the reference step's change||: the error in the update
  itself (momentum 0, so the clipped gradient times lr). bfloat16
  0.0065-0.010, float8 0.11-0.53.
- round_std 0.08: a whole round (20 steps on one batch, then the forecast,
  denormalized) against the reference's: the largest |difference| of a
  feature in units of that feature's standard deviation over the series
  (the model's own units). Twenty steps compound the update's error:
  bfloat16 0.007-0.037, float8 e4m3 0.12-0.83.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import forecast_reference as ref  # noqa: E402

TOLERANCES = {"forecast_rel": 0.03, "loss_rel": 0.02, "step_rel": 0.04,
              "round_std": 0.08}
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "topic-telemetry-forecast.json")
SERIES = os.path.join(ROOT, "scripts", "data", "forecast_series_tpu_v5e.csv")
DIMS = ("d_model", "n_heads", "d_ff", "n_layers", "n_features", "seq_len")


def program_cfg(dims: dict, dtype=None):
    import jax.numpy as jnp

    from chanamq_tpu.models.forecaster import ForecasterConfig

    return ForecasterConfig(dtype=jnp.bfloat16 if dtype is None else dtype,
                            **{k: dims[k] for k in DIMS})


def host(tree: dict) -> dict:
    return {k: np.asarray(v, dtype=np.float32) for k, v in tree.items()}


def program_params(dims: dict, seed: int, dtype=None) -> dict:
    import jax

    from chanamq_tpu.models.forecaster import init_params

    return init_params(jax.random.PRNGKey(seed), program_cfg(dims, dtype))


def normal_batch(dims: dict, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((batch, dims["seq_len"], dims["n_features"]))
    y = rng.standard_normal((batch, dims["n_features"]))
    return x.astype(np.float32), y.astype(np.float32)


def series_batch(series: np.ndarray, dims: dict, batch: int, seed: int = 0):
    """The program's batch of a round over `series`: its normalization and
    its training_batch, with the service's generator seeded as it is."""
    from chanamq_tpu.models.telemetry import normalization, training_batch

    mean, std = normalization(series)
    return training_batch((series - mean) / std, dims["seq_len"], batch,
                          np.random.default_rng(seed))


def forward_error(dims: dict, x: np.ndarray, seed: int = 0,
                  dtype=None) -> float:
    import jax

    from chanamq_tpu.models.forecaster import forward

    cfg = program_cfg(dims, dtype)
    got = np.asarray(jax.jit(lambda p, w: forward(p, w, cfg))(
        program_params(dims, seed, dtype), x))
    want = np.asarray(ref.forward(ref.init_params(seed, dims), x, dims))
    return float(np.abs(got - want).max() / np.abs(want).max())


def step_errors(dims: dict, x: np.ndarray, y: np.ndarray, seed: int = 0,
                dtype=None) -> dict:
    """One train step from the seeded params: the loss's and the updated
    params' error against the reference's step."""
    import jax

    from chanamq_tpu.models.forecaster import init_momentum, make_train_step

    params = program_params(dims, seed, dtype)
    new, _, loss = jax.jit(make_train_step(program_cfg(dims, dtype)))(
        params, init_momentum(params), (x, y))
    before = ref.init_params(seed, dims)
    want, _, want_loss = ref.train_step(
        before, {k: np.zeros_like(v) for k, v in before.items()}, x, y, dims)
    new, want = host(new), host(want)
    off = np.sqrt(sum(np.sum((new[k] - want[k]) ** 2) for k in new))
    moved = np.sqrt(sum(np.sum((want[k] - before[k]) ** 2) for k in new))
    return {"loss_rel": abs(float(loss) - float(want_loss))
            / abs(float(want_loss)),
            "step_rel": float(off / moved),
            "step_max_abs": float(max(np.abs(new[k] - want[k]).max()
                                      for k in new))}


def round_service(dims: dict, batch: int, steps: int = 20, lr: float = 1e-3,
                  dtype=None):
    """A fresh ForecastService at these widths, its step and forward built
    and compiled (`_warm`), its first round not yet run."""
    from chanamq_tpu.broker.broker import Broker
    from chanamq_tpu.models.service import ForecastService
    from chanamq_tpu.store.memory import MemoryStore

    kwargs = {k: dims[k] for k in ("d_model", "n_heads", "d_ff", "n_layers")}
    if dtype is not None:
        kwargs["dtype"] = dtype
    service = ForecastService(
        Broker(store=MemoryStore(), message_sweep_interval_s=3600.0),
        seq_len=dims["seq_len"], batch=batch, steps_per_round=steps, lr=lr,
        model_kwargs=kwargs)
    service._warm()
    return service


def round_error(service, history: np.ndarray) -> float:
    """The service's first round over `history` against the reference's, in
    units of each feature's standard deviation over `history`."""
    _, _, got = service._round(history)
    dims = dict(service.model_kwargs, n_features=service.n_features,
                seq_len=service.seq_len)
    want, std = ref.round_forecast(history, dims, service.batch,
                                   service.steps_per_round, lr=service.lr)
    got_v = np.array([got[n] for n in service.feature_names])
    return float((np.abs(got_v - want) / std).max())


def compare(dims: dict, batch: int, steps: int, lr: float,
            series: np.ndarray, dtype=None) -> dict:
    """Every reading of one variant: the forward and one step on seeded
    normal batches and on the series' batch, and a whole round."""
    out = {}
    inputs = {f"normal_{seed}": normal_batch(dims, batch, seed)
              for seed in (0, 1)}
    inputs["series"] = series_batch(series, dims, batch)
    for name, (x, y) in inputs.items():
        out[name] = dict(step_errors(dims, x, y, dtype=dtype),
                         forecast_rel=forward_error(dims, x, dtype=dtype))
    out["round"] = {"round_std": round_error(
        round_service(dims, batch, steps, lr, dtype), series)}
    return out


def outside(readings: dict) -> list:
    """The (input, reading) pairs that exceed their tolerance."""
    return [(name, key) for name, got in readings.items()
            for key, limit in TOLERANCES.items()
            if key in got and got[key] > limit]


def read_series(path: str) -> np.ndarray:
    from chanamq_tpu.models.telemetry import FEATURES

    with open(path, encoding="utf-8") as f:
        header = f.readline().strip().split(",")
    if tuple(header) != FEATURES:
        raise ValueError(f"{path}: columns {header}, want {list(FEATURES)}")
    return np.loadtxt(path, delimiter=",", skiprows=1, dtype=np.float32,
                      ndmin=2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=CONFIG,
                    help="a benchmark configuration with a `model` block")
    ap.add_argument("--series", default=SERIES)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    with open(args.config, encoding="utf-8") as f:
        model = json.load(f)["model"]
    dims = {k: model[k] for k in DIMS}
    batch, steps = model["batch"], model["steps_per_round"]
    series = read_series(args.series)
    result = {"device": jax.devices()[0].device_kind, "dims": dims,
              "batch": batch, "ticks": len(series),
              "tolerances": TOLERANCES}
    ok = True
    for name, dtype in (("bfloat16", None),
                        ("float8_e4m3fn", jnp.float8_e4m3fn),
                        ("float8_e5m2", jnp.float8_e5m2)):
        readings = compare(dims, batch, steps, model["lr"], series, dtype)
        result[name] = readings
        over = outside(readings)
        result[name + "_outside"] = [".".join(pair) for pair in over]
        ok &= (not over) if dtype is None else bool(over)
    result["ok"] = ok
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(line + "\n")
    print(line)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
