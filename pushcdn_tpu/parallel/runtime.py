"""Process start-up for every entry point that puts JAX on a device:
``bin/broker --device-plane`` / ``--mesh-shards``, ``bench.py``,
``__graft_entry__.py`` and the chip smoke's JAX children all call
:func:`init` before their first array.

It settles four things once, in one place:

- **Where compiled programs are kept.** If ``JAX_COMPILATION_CACHE_DIR``
  is set, JAX reads it itself and nothing is set in code. Otherwise the
  cache is the fixed ``<checkout>/.build/jax_cache`` — never a temp name,
  pid or timestamp: the directory is part of every entry's key, so a
  cache that moves never hits. The minimum-compile-time threshold is
  dropped to 0: the device plane compiles a small step specialization
  per 64-user bucket and lane-set shape, each well under JAX's 1 s
  default, and every one of them stalls the pump thread on first use —
  caching them all costs kilobytes and spares a restarted broker up to
  32 of those stalls. A process that asked for the CPU gets no cache
  placed in code: XLA:CPU entries are tied to the build host's CPU
  features (loading one elsewhere logs a SIGILL warning per program)
  and CPU compiles of these programs take milliseconds.
- **Which device the process got.** JAX drops to the CPU on its own when
  no accelerator initialises; a data plane that meant to run on a chip
  must not serve (or report numbers) from that CPU. Unless the caller
  asked for it with an explicit ``JAX_PLATFORMS=cpu`` (as the tests do),
  a CPU backend is refused.
- **What compiling cost.** :class:`CompileStats` sums the seconds JAX
  spent obtaining executables (backend compile, or a persistent-cache
  load) and counts cache hits and misses, from JAX's own monitoring
  events.
- **Where host spans go.** :func:`pushcdn_tpu.parallel.spans.span` is a
  no-op until here; from here on it is ``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import logging
import os
from typing import NamedTuple, Optional

logger = logging.getLogger("pushcdn.runtime")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO, ".build", "jax_cache")


class Device(NamedTuple):
    """The backend as JAX reports it."""

    platform: str  # jax.devices()[0].platform
    kind: str      # jax.devices()[0].device_kind
    count: int     # len(jax.devices())


class CompileStats:
    """Running totals of this process's compilations since construction
    (listeners cannot be unregistered through JAX's public API, so make
    one per process, not one per step)."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.seconds = 0.0     # backend compiles + persistent-cache loads
        self.programs = 0      # executables obtained either way
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": round(self.seconds, 3),
                "programs": self.programs,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class Runtime(NamedTuple):
    device: Device
    cache_dir: Optional[str]  # None: no persistent cache in this process
    compiles: CompileStats


def cpu_requested() -> bool:
    """True when the environment names the CPU platform on purpose."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def device() -> Device:
    """The default backend (initialises it on first call)."""
    import jax
    devices = jax.devices()
    return Device(devices[0].platform, devices[0].device_kind, len(devices))


def memory_peak_bytes() -> int:
    """Peak bytes in use on this process's fullest device (0 where the
    backend does not say, as the CPU)."""
    import jax
    return max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()), default=0)


def init(who: str) -> Runtime:
    """Place the compile cache, start compile accounting, bind the host
    spans to the profiler, initialise the backend and refuse a CPU nobody
    asked for. ``who`` names the caller in the log line and the refusal."""
    import jax

    from pushcdn_tpu.parallel import spans
    spans.bind()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if cache_dir is None and not cpu_requested():
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileStats()
    dev = device()
    if dev.platform == "cpu" and not cpu_requested():
        raise SystemExit(
            f"{who}: JAX found no accelerator and fell back to the CPU "
            f"({dev.count} x {dev.kind}); refusing to run a device path on "
            "it. Set JAX_PLATFORMS=cpu to run on the CPU on purpose.")
    logger.info("%s: platform=%s device_kind=%s devices=%d compile_cache=%s",
                who, dev.platform, dev.kind, dev.count, cache_dir)
    return Runtime(dev, cache_dir, compiles)
