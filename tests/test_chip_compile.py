"""The device path's programs, compiled for the real chip without one.

The TPU compiler is installed beside the CPU-only test environment and
compiles for a chip that is described, not attached (the
`on-chip-measurement` guide, section 2, rehearsal 3). These tests hand it
the router's two match kernels and the forecaster's train step and forward
at the shapes the broker really uses, so a change the chip's compiler
would refuse is caught here, at no chip time. A compile is not a run: it
says nothing about results or speed — `chip_smoke.py` on the chip does.

All in this one file, the topology described inside a module-scoped
fixture (only one process may load the TPU library; never at import,
never in a child process), and the persistent compile cache off around
them (an entry compiled for a described chip cannot be read back here).
"""

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chanamq_tpu.models.forecaster import (  # noqa: E402
    ForecasterConfig, forward, init_momentum, init_params, make_train_step,
)
from chanamq_tpu.router.compile import (  # noqa: E402
    MAX_PATTERN_WORDS, _headers_kernel, _split_topic, _topic_kernel,
)

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip

# The router's caps (chana.mq.router.max-wildcards / max-queues defaults)
# and the largest batch bucket a flush can reach: the connection reads
# 262,144 bytes at a time and the smallest publish is 40 wire bytes, so
# one flush carries fewer than 8,192 messages.
MAX_ROWS = 512
MAX_MASK_WORDS = 4096 // 32
MAX_BATCH = 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    log_dir_was = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs in /tmp
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:  # noqa: BLE001 — any reason means "skip"
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        if log_dir_was is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir_was
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    print(f"\n{mem}")
    assert 0 < need < HBM_BYTES


def _described(tree, sharding):
    """The same shapes and dtypes, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding), tree)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


@pytest.mark.parametrize("batch,rows,pre,suf,mask_words", [
    pytest.param(16, 4, 2, 2, 1, id="floor"),
    # what chip_smoke.py's topic table compiles to (512 rows, 512 queues)
    pytest.param(1024, MAX_ROWS, 4, 2, 16, id="smoke"),
    pytest.param(MAX_BATCH, MAX_ROWS, MAX_PATTERN_WORDS, MAX_PATTERN_WORDS,
                 MAX_MASK_WORDS, id="caps"),
])
@pytest.mark.parametrize("packed", [False, True], ids=["body", "launch"])
def test_topic_kernel_compiles_for_v5e(one_chip, batch, rows, pre, suf,
                                       mask_words, packed):
    """The kernel's body over its three batch operands, and the program a
    launch really runs: the batch as one array, sliced under the jit."""
    tables = [
        ((rows, pre), jnp.int32), ((rows, suf), jnp.int32),
        ((rows,), jnp.int32), ((rows,), jnp.int32), ((rows,), jnp.bool_),
        ((rows, mask_words), jnp.uint32)]
    if packed:
        _compile(
            lambda *a: _topic_kernel(
                jnp, *a[:6], *_split_topic(a[6], pre, suf)),
            *_shapes(one_chip, *tables, ((batch, pre + suf + 1), jnp.int32)))
    else:
        _compile(lambda *a: _topic_kernel(jnp, *a), *_shapes(
            one_chip, *tables,
            ((batch, pre), jnp.int32), ((batch, suf), jnp.int32),
            ((batch,), jnp.int32)))


@pytest.mark.parametrize("batch,rows,required,present,mask_words", [
    pytest.param(16, 4, 2, 2, 1, id="floor"),
    # chip_smoke.py's headers table: 256 bindings of 1-3 pairs, 128 queues
    pytest.param(1024, 256, 4, 4, 4, id="smoke"),
    pytest.param(MAX_BATCH, MAX_ROWS, MAX_PATTERN_WORDS, MAX_PATTERN_WORDS,
                 MAX_MASK_WORDS, id="caps"),
])
def test_headers_kernel_compiles_for_v5e(one_chip, batch, rows, required,
                                         present, mask_words):
    _compile(lambda *a: _headers_kernel(jnp, *a), *_shapes(
        one_chip,
        ((rows, required), jnp.int32), ((rows,), jnp.int32),
        ((rows,), jnp.bool_), ((rows, mask_words), jnp.uint32),
        ((batch, present), jnp.int32)))


# ForecastService's own model (models/service.py defaults): it trains on
# batches of 16 windows and predicts from one. ForecasterConfig()'s
# defaults are what __graft_entry__.entry() jits, at batch 32.
SERVICE_CFG = ForecasterConfig(d_model=64, n_heads=4, d_ff=256, n_layers=2)


@pytest.mark.parametrize("cfg,batch,train", [
    pytest.param(SERVICE_CFG, 16, True, id="service-train"),
    pytest.param(SERVICE_CFG, 1, False, id="service-forward"),
    pytest.param(ForecasterConfig(), 32, True, id="defaults-train"),
    pytest.param(ForecasterConfig(), 32, False, id="defaults-forward"),
])
def test_forecaster_compiles_for_v5e(one_chip, cfg, batch, train):
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct(
        (batch, cfg.seq_len, cfg.n_features), jnp.float32)
    if train:
        y = jax.ShapeDtypeStruct((batch, cfg.n_features), jnp.float32)
        _compile(make_train_step(cfg), *_described(
            (params, jax.eval_shape(init_momentum, params), (x, y)),
            one_chip))
    else:
        _compile(lambda p, w: forward(p, w, cfg),
                 *_described((params, x), one_chip))
