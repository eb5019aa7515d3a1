"""The device path's door to JAX.

Every module of the device path imports this one next to its `import jax`,
so three things are decided in exactly one place:

  * where compiled programs are kept. `JAX_COMPILATION_CACHE_DIR` set: JAX
    reads it and nothing here touches it. Unset: `<checkout>/.jax_cache`,
    computed from this file's own path — a fixed name (the path is part of
    the cache key's neighbourhood: a directory that moves never hits), so
    a fresh worker process finds what the last one compiled;
  * what a device label means. `device="tpu"` is the TPU or an error
    (`require_device`); "cpu-jax" is XLA:CPU, the name the tests use. A
    label is never just a label: `CompactionStats.device` routes a job to
    the DCOMPACTION_* tickers, so it has to be what JAX ran on;
  * how compilations are counted (`count_compiles`), so a job can say how
    many programs it compiled and how many came from the cache;
  * that the program's spans reach the profiler. `utils/telemetry.py`
    imports no JAX (the DB process never loads it); here, in the process
    that does, `jax.profiler.TraceAnnotation` becomes its mirror: every
    real span is also an annotation on the span's own thread. Without a
    profiler session that is one atomic load a span; with one, the spans
    lie in the trace's host plane beside the device's ops, on one clock.
"""

from __future__ import annotations

import contextlib
import os

import jax

from toplingdb_tpu.utils import telemetry
from toplingdb_tpu.utils.status import InvalidArgument, NotSupported

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(_CHECKOUT, ".jax_cache"))
# Keep every program, not only the ones that took over a second: a shape
# bucket a worker has met once is never compiled by the next worker.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

telemetry.set_mirror(jax.profiler.TraceAnnotation)

# Device label -> the platform jax.devices()[0] must report.
_PLATFORM_OF = {"tpu": "tpu", "cpu-jax": "cpu"}


def require_device(device: str):
    """The first JAX device, after checking it is what `device` names.
    Raises NotSupported when JAX runs on another platform — a job asked
    to run on the TPU never runs, or is reported, anywhere else."""
    want = _PLATFORM_OF.get(device)
    if want is None:
        raise InvalidArgument(
            f"unknown device {device!r}; one of {sorted(_PLATFORM_OF)}")
    dev = jax.devices()[0]
    if dev.platform != want:
        raise NotSupported(
            f"device={device!r} needs a JAX {want!r} backend, but "
            f"jax.devices()[0] is {dev.platform!r} ({dev.device_kind})")
    return dev


def describe_devices() -> dict:
    """What JAX reports, for /health, /stats and the smoke's record."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_memory() -> list[dict]:
    """Per-device allocator peaks as JAX reports them — which chips a
    process has really used. Empty where the backend keeps no such
    counters (XLA:CPU)."""
    out = []
    for d in jax.devices():
        ms = d.memory_stats()
        if ms:
            out.append({"id": d.id,
                        "peak_bytes_in_use": ms.get("peak_bytes_in_use", 0)})
    return out


class CompileCount:
    """Programs requested from XLA while a `count_compiles` block ran."""

    def __init__(self):
        self.requests = 0     # jit cache misses that reached the compiler
        self.cache_hits = 0   # of those, served by the persistent cache
        self.seconds = 0.0    # wall inside compile-or-load, hits included

    @property
    def compiled(self) -> int:
        return self.requests - self.cache_hits


# The count_compiles blocks that are running, innermost last.
_counting: list[CompileCount] = []


def compiles_now() -> tuple[int, int]:
    """(programs compiled, programs loaded from the persistent cache) so
    far in the innermost running `count_compiles` block; (0, 0) outside
    one. A dispatch reads it before and after to say whether it compiled
    (the span `pipeline.dispatch`)."""
    if not _counting:
        return 0, 0
    c = _counting[-1]
    return c.compiled, c.cache_hits


@contextlib.contextmanager
def count_compiles():
    """Count XLA compile requests process-wide (the compiling thread is
    often a pipeline worker, not the caller) for the block's duration."""
    c = CompileCount()

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            c.cache_hits += 1

    def on_duration(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            c.requests += 1
            c.seconds += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _counting.append(c)
    try:
        yield c
    finally:
        _counting.remove(c)
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)
