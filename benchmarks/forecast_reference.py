"""The broker's telemetry forecaster written out plainly, for the comparison
that holds the program's forecaster to its mathematics, and the count of its
operations. It imports nothing of the program: the parameters are a dict of
named float32 arrays (`embed/kernel`, `pos`, `layer0/attn/qkv`, ...), the
names the program's parameter tree carries, and `dims` holds the widths the
configuration's `model` block states (d_model, n_heads, d_ff, n_layers,
n_features, seq_len).

It draws what it computes on from a seed itself, as the service does: the
parameters from `jax.random.PRNGKey(seed)` (`init_params`), a round's batch
of windows from `numpy.random.default_rng(seed)` (`training_batch`), and a
whole round from the telemetry history alone (`round_forecast`).

Everything computes in float32 under `jax.default_matmul_precision
("highest")`: embedding plus position, pre-LN causal attention, a GELU MLP,
the last position's readout; the mean squared error of that readout; its
gradient; SGD with momentum 0.9 after clipping the gradient's global norm.

Where it departs from the program (chanamq_tpu/models/forecaster.py), on
purpose:
- the program casts the residual stream and every matmul's inputs to
  bfloat16 and accumulates in float32; here nothing is rounded below
  float32 (that rounding is what the comparison's tolerances measure);
- the program splits one fused qkv matmul into heads by reshape and
  transpose; here each head's slice is taken from the same fused product;
- the program masks the causal logits with -1e30 before its softmax; here
  the masked entries are -inf, which the softmax maps to exactly 0;
- the gradient is `jax.grad` of the loss below, not of the program's loss.

jax is imported inside the functions that compute, never at module level,
so the operation counts (`forward_flops`, `train_step_flops`) can be read by
a process that must not import jax.
"""

from __future__ import annotations

import math

import numpy as np

MOMENTUM = 0.9
LN_EPS = 1e-6
STD_FLOOR = 1e-3


def forward_flops(dims: dict, batch: int) -> int:
    """Multiply-adds x 2 of one forward over `batch` windows. The causal
    attention is counted as the full seq_len x seq_len product, which is
    what the program computes before it masks."""
    t, d, f = dims["seq_len"], dims["d_model"], dims["n_features"]
    ff, layers = dims["d_ff"], dims["n_layers"]
    tokens = batch * t
    per_layer = (2 * tokens * d * 3 * d      # fused q, k, v
                 + 2 * 2 * batch * t * t * d  # logits and weights x values
                 + 2 * tokens * d * d         # output projection
                 + 2 * 2 * tokens * d * ff)   # the MLP's two matmuls
    return (2 * tokens * f * d                # embedding
            + layers * per_layer
            + 2 * batch * d * f)              # readout of the last position


def train_step_flops(dims: dict, batch: int) -> int:
    """One train step: the forward and its backward, which costs twice the
    forward (a gradient for each matmul's two inputs)."""
    return 3 * forward_flops(dims, batch)


def _layernorm(x, scale):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale


def _gelu(x):
    """The tanh form, which jax.nn.gelu computes by default."""
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, qkv, proj, n_heads: int):
    import jax
    import jax.numpy as jnp

    _, t, d = x.shape
    hd = d // n_heads
    fused = x @ qkv
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    heads = []
    for h in range(n_heads):
        q = fused[..., h * hd:(h + 1) * hd]
        k = fused[..., d + h * hd:d + (h + 1) * hd]
        v = fused[..., 2 * d + h * hd:2 * d + (h + 1) * hd]
        logits = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(hd)
        logits = jnp.where(causal, logits, -jnp.inf)
        heads.append(jax.nn.softmax(logits, axis=-1) @ v)
    return jnp.concatenate(heads, axis=-1) @ proj


def forward(params: dict, x, dims: dict):
    """x [batch, seq_len, n_features] -> forecast [batch, n_features]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = jnp.asarray(x, jnp.float32)
        h = x @ p["embed/kernel"] + p["embed/bias"] + p["pos"][:x.shape[1]]
        for layer in range(dims["n_layers"]):
            pre = f"layer{layer}"
            a = _layernorm(h, p[f"{pre}/ln1/scale"])
            h = h + _attention(a, p[f"{pre}/attn/qkv"], p[f"{pre}/attn/proj"],
                               dims["n_heads"])
            m = _layernorm(h, p[f"{pre}/ln2/scale"])
            h = h + _gelu(m @ p[f"{pre}/mlp/w1"]) @ p[f"{pre}/mlp/w2"]
        return h[:, -1, :] @ p["out/kernel"] + p["out/bias"]


def loss(params: dict, x, y, dims: dict):
    import jax.numpy as jnp

    return jnp.mean((forward(params, x, dims) - jnp.asarray(y)) ** 2)


def train_step(params: dict, momentum: dict, x, y, dims: dict,
               lr: float = 1e-3, clip_norm: float = 1.0):
    """(new params, new momentum, loss) of one step, all float32."""
    import jax
    import jax.numpy as jnp

    value, grads = jax.value_and_grad(loss)(params, x, y, dims)
    with jax.default_matmul_precision("highest"):
        squares = sum(jnp.sum(g ** 2) for g in grads.values())
        scale = jnp.minimum(1.0, clip_norm / jnp.sqrt(squares + 1e-12))
        new_momentum = {k: MOMENTUM * jnp.asarray(momentum[k], jnp.float32)
                        + grads[k] * scale for k in params}
        new_params = {k: jnp.asarray(params[k], jnp.float32)
                      - lr * new_momentum[k] for k in params}
    return new_params, new_momentum, value


def init_params(seed: int, dims: dict) -> dict:
    """The service's initial parameters, drawn from `PRNGKey(seed)` split
    into 4 + 6 a layer keys, taken in order: the embedding, the positions,
    the readout, then the attention's two and the MLP's two of each layer
    (the rest go unused). Each kernel is standard normal over the square
    root of its fan-in, the positions at 0.02, biases 0, layer-norm scales
    1."""
    import jax

    d, f = dims["d_model"], dims["n_features"]
    ff, layers = dims["d_ff"], dims["n_layers"]
    keys = list(jax.random.split(jax.random.PRNGKey(seed), 4 + 6 * layers))

    def normal(key, shape, scale):
        return np.asarray(jax.random.normal(key, shape) * scale, np.float32)

    p = {
        "embed/kernel": normal(keys[0], (f, d), 1.0 / math.sqrt(f)),
        "embed/bias": np.zeros(d, np.float32),
        "pos": normal(keys[1], (dims["seq_len"], d), 0.02),
        "out/kernel": normal(keys[2], (d, f), 1.0 / math.sqrt(d)),
        "out/bias": np.zeros(f, np.float32),
    }
    for layer in range(layers):
        pre, k = f"layer{layer}", keys[3 + 4 * layer:]
        p[f"{pre}/ln1/scale"] = np.ones(d, np.float32)
        p[f"{pre}/ln2/scale"] = np.ones(d, np.float32)
        p[f"{pre}/attn/qkv"] = normal(k[0], (d, 3 * d), 1.0 / math.sqrt(d))
        p[f"{pre}/attn/proj"] = normal(k[1], (d, d), 1.0 / math.sqrt(d))
        p[f"{pre}/mlp/w1"] = normal(k[2], (d, ff), 1.0 / math.sqrt(d))
        p[f"{pre}/mlp/w2"] = normal(k[3], (ff, d), 1.0 / math.sqrt(ff))
    return p


def normalize(history: np.ndarray):
    """(z-scored history, mean, std), the std floored at STD_FLOOR."""
    history = np.asarray(history, np.float64)
    mean = history.mean(axis=0)
    std = np.maximum(history.std(axis=0), STD_FLOOR)
    return ((history - mean) / std).astype(np.float32), mean, std


def training_batch(series: np.ndarray, seq_len: int, batch: int, seed: int):
    """(x [batch, seq_len, features], y [batch, features]): `batch` windows
    of `series` and the vector after each, their starts drawn uniformly by
    `numpy.random.default_rng(seed)`."""
    starts = np.random.default_rng(seed).integers(
        0, len(series) - seq_len, size=batch)
    x = np.stack([series[s:s + seq_len] for s in starts])
    y = np.stack([series[s + seq_len] for s in starts])
    return x.astype(np.float32), y.astype(np.float32)


def round_forecast(history: np.ndarray, dims: dict, batch: int, steps: int,
                   lr: float = 1e-3, seed: int = 0):
    """A fresh service's first round over a telemetry history [ticks,
    features]: z-score it, draw one batch, take `steps` steps on that batch
    from the seeded parameters and zero momentum, forecast the next tick
    from the newest window. Returns (the forecast in real units, floored at
    0, and the history's per-feature std)."""
    normed, mean, std = normalize(history)
    x, y = training_batch(normed, dims["seq_len"], batch, seed)
    params = init_params(seed, dims)
    momentum = {k: np.zeros_like(v) for k, v in params.items()}
    for _ in range(steps):
        params, momentum, _ = train_step(params, momentum, x, y, dims, lr=lr)
    pred = np.asarray(forward(params, normed[None, -dims["seq_len"]:], dims),
                      np.float64)[0]
    return np.maximum(pred * std + mean, 0.0), std
