"""The accelerator this process runs its JAX code on: claimed once, at
boot, and named.

A chip belongs to one process at a time. The two places that put work
on it — the tensor router's match kernels (router/compile.py) and the
forecaster's train/predict round (models/service.py) — call ``claim()``
where they are built, before the first ``jax.jit``, so that

- the backend comes up at boot, not in the middle of traffic;
- the process says which device it got (one INFO line; the same values
  at ``GET /admin/overview`` for a parent that must stay off JAX);
- a process that did not get the chip refuses to boot instead of quietly
  routing on the CPU: the only way onto the CPU is ``JAX_PLATFORMS=cpu``;
- both share one persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``
  where it is set (JAX reads it itself, nothing here overrides it),
  otherwise ``<checkout>/.jax_cache`` — a fixed path, because the path is
  part of the cache key and a directory that moves never hits.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Optional

from .config import ConfigError

log = logging.getLogger("chanamq.device")

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def check_platform(found: str, asked: Optional[str]) -> None:
    """The no-quiet-CPU rule: ``found`` is the platform JAX came up on,
    ``asked`` the value of ``JAX_PLATFORMS`` (None/"" = not set). The TPU
    is always fine; anything else only when ``JAX_PLATFORMS`` names it
    FIRST. A fallback further down the list (``tpu,cpu``, which is what a
    TPU host's environment may carry) is not asking for the CPU."""
    if found == "tpu":
        return
    first = (asked or "").split(",")[0].strip().lower()
    if found.lower() == first:
        return
    raise ConfigError(
        f"JAX came up on {found!r}, not on the TPU, and JAX_PLATFORMS "
        f"({asked!r}) did not ask for that first. Either this machine has "
        "no chip or another process holds it (a chip belongs to one process "
        f"at a time). Set JAX_PLATFORMS={found} to run there deliberately, "
        "or run this process without JAX (chana.mq.router.backend=python, "
        "chana.mq.forecast.enabled=false).")


class Device:
    """What ``claim()`` found, plus this process's compile-cache counts."""

    __slots__ = ("platform", "kind", "count", "cache_dir", "cache_hits",
                 "cache_misses", "_lock")

    def __init__(self, platform: str, kind: str, count: int,
                 cache_dir: str) -> None:
        self.platform = platform
        self.kind = kind
        self.count = count
        self.cache_dir = cache_dir
        self.cache_hits = 0
        self.cache_misses = 0
        # compiles happen on the event loop (router) and on the
        # forecaster's worker thread
        self._lock = threading.Lock()

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1
        elif event == _CACHE_MISS:
            with self._lock:
                self.cache_misses += 1

    def snapshot(self) -> dict:
        return {
            "platform": self.platform,
            "kind": self.kind,
            "count": self.count,
            "compile_cache": {
                "dir": self.cache_dir,
                "hits": self.cache_hits,
                "misses": self.cache_misses,
            },
        }


_claimed: Optional[Device] = None


# what ``span()`` hands out while no profiler session can record
_NO_SPAN = contextlib.nullcontext()
# jax.profiler.TraceAnnotation once a device is claimed (this module owns
# the JAX import: a process on router.backend=python never loads it)
_annotation = None


def span(name: str):
    """A context manager that puts ``name`` on the calling thread's line of
    the profiler's trace, on the device trace's clock, so an idle gap of the
    chip can be credited to what the host was doing meanwhile. Outside a
    profiler session (one C call tells), and in a process that claimed no
    device, it is a shared no-op. Spans are written flat: open none inside
    another, and none across an ``await`` (trace readers credit a stretch to
    the outermost event and would lose everything inside)."""
    if _annotation is None or not _annotation.is_enabled():
        return _NO_SPAN
    return _annotation(name)


def claimed() -> Optional[Device]:
    """The device this process holds, or None when nothing claimed one
    (router backend python and no forecaster: JAX was never imported)."""
    return _claimed


def claim() -> Device:
    """Bring JAX's backend up, check it against the rule above, point the
    compile cache, and log the device. Idempotent: the backend is a
    process-wide fact, so every later call returns the first result."""
    global _claimed, _annotation
    if _claimed is not None:
        return _claimed
    try:
        import jax
    except ImportError as exc:
        raise ConfigError(
            "chana.mq.router.backend=jax and chana.mq.forecast.enabled "
            f"need jax + numpy; import failed: {exc}") from None
    asked = os.environ.get("JAX_PLATFORMS")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the router kernels compile in about a second — right at JAX's default
    # threshold for keeping an entry; a second boot must find them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise ConfigError(
            f"JAX found no usable backend (JAX_PLATFORMS={asked!r}): {exc}. "
            "A chip belongs to one process at a time — is another process "
            "holding it?") from exc
    first = devices[0]
    check_platform(first.platform, asked)
    device = Device(first.platform, first.device_kind, len(devices),
                    cache_dir)
    jax.monitoring.register_event_listener(device._on_event)
    log.info(
        "device claimed by pid %d: platform=%s kind=%s count=%d "
        "(JAX_PLATFORMS=%s, compile cache %s)", os.getpid(),
        device.platform, device.kind, device.count, asked or "unset",
        cache_dir)
    _claimed = device
    _annotation = jax.profiler.TraceAnnotation
    return device
