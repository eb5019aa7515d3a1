"""Mesh-sharded device compaction: one job's uniform key-range shards
fanned out over every chip of a (jobs=1, range=R) `jax.sharding.Mesh`.

The single-chip plane (ops/device_compaction.py) already splits a big job
into presorted uniform shards and runs one fused merge+GC program per
shard; those programs carry no device pin — the committed inputs decide
where they run. Mesh mode is therefore placement, not a new kernel: each
shard's `upload_uniform_shard` buffers are committed to a chip picked
round-robin from the mesh's range axis, so S shards execute on D chips
concurrently while the host streams finishes in shard order into the
same block/zip writers. Outputs are byte-identical to the single-chip
path BY CONSTRUCTION (same per-shard kernel, same per-shard inputs, same
stitch order).

Dispatch is double-buffered per chip (mesh_plan.UPLOAD_DEPTH uploads in
flight per device): shard s+D's H2D transfer streams while shard s
computes on the same chip, and every program's D2H copies are enqueued at
dispatch, so the writer's encode overlaps the remaining chips' compute.

Gating: `TPULSM_MESH_COMPACT=1` enables the mode; ineligible jobs
(complex merge groups, non-uniform shards, below the row floor, a single
shard/device) fall back to the serial single-device plane automatically —
mesh_plan.check_eligibility is the one fallback matrix. A chip that
fails mid-job is WEDGED: its queued shards re-dispatch onto the surviving
chips (or the default device when none remain) and the job completes with
the same bytes; the demotion is counted on CompactionStats.mesh_fallbacks
and visible as a `compaction.mesh.fallback` span event beside the
`pipeline.merge_gc` spans, whose `chip=` tag names the chip a shard ran
on, in the stitched waterfall.
"""

from __future__ import annotations

import os

from toplingdb_tpu.parallel import mesh_plan
from toplingdb_tpu.utils import errors as _errors
from toplingdb_tpu.utils import telemetry
from toplingdb_tpu.utils.status import NotSupported

# Test seam: callable(shard_idx, device) invoked before each dispatch;
# raising simulates a chip failure at that point (chaos/demotion tests).
_FAULT_HOOK = None


def mesh_enabled() -> bool:
    return os.environ.get("TPULSM_MESH_COMPACT") == "1"


def maybe_plan(shards, any_complex: bool = False, stats=None,
               trace=None):
    """A MeshPlan when the knob is on and the job is eligible, else None.
    Eligibility misses while the knob is ON are fallbacks: counted on
    `stats.mesh_fallbacks` and emitted as a `compaction.mesh.fallback`
    event so waterfalls show WHY a job stayed single-chip."""
    if not mesh_enabled():
        return None
    try:
        devices = mesh_plan.mesh_devices()
    except Exception as e:  # no jax backend at all → serial plane
        _errors.swallow(reason="mesh-no-backend", exc=e)
        devices = []
    plan, reason = mesh_plan.plan_shards(shards, any_complex, devices)
    if plan is None:
        if stats is not None:
            stats.mesh_fallbacks = getattr(stats, "mesh_fallbacks", 0) + 1
        telemetry.span_event_under(trace, "compaction.mesh.fallback", 0,
                                   reason=reason)
        return None
    if stats is not None:
        stats.mesh_chips = plan.n_devices
        stats.mesh_shards = len(shards)
    return plan


class MeshShardRun:
    """Windowed round-robin dispatch of one job's shards over a plan's
    chips. `finish(s)` must be called for s = 0..n_shards-1 in order (the
    writers consume survivor orders in shard order); each finish tops the
    dispatch window back up, keeping every chip double-buffered.

    plan=None is the serial twin: every shard uploads up front to the
    default device — exactly the single-chip plane's dispatch, so the
    bench's 1-chip runs and mesh runs share this driver."""

    def __init__(self, plan, shards, cover, snapshots, bottommost,
                 stats=None, trace=None):
        from toplingdb_tpu.ops import compaction_kernels as ck

        self._ck = ck
        self._plan = plan
        self._shards = shards
        self._cover = cover
        self._snapshots = snapshots
        self._bottommost = bottommost
        self._stats = stats
        self._trace = trace
        self._mesh = (mesh_plan.build_range_mesh(plan.devices)
                      if plan is not None else None)
        self._wedged: set[int] = set()
        self._pend: dict[int, tuple] = {}
        self._next = 0
        self._window = plan.window if plan is not None else len(shards)
        self._fill()

    # -- placement ---------------------------------------------------------

    def _device_for(self, s: int):
        """Shard s's chip: the plan's round-robin assignment, re-mapped
        onto the surviving chips once any are wedged; None (= default
        device) when no planned chip survives."""
        if self._plan is None:
            return None
        if not self._wedged:
            return self._plan.devices[self._plan.assignments[s]]
        healthy = [d for i, d in enumerate(self._plan.devices)
                   if i not in self._wedged]
        if not healthy:
            return None
        return healthy[s % len(healthy)]

    def _wedge(self, device, exc) -> None:
        if self._plan is None or device is None:
            return
        for i, d in enumerate(self._plan.devices):
            if d is device and i not in self._wedged:
                self._wedged.add(i)
                if self._stats is not None:
                    self._stats.mesh_fallbacks = getattr(
                        self._stats, "mesh_fallbacks", 0) + 1
                    self._stats.mesh_chips = max(
                        1, len(self._plan.devices) - len(self._wedged))
                telemetry.span_event_under(
                    self._trace, "compaction.mesh.fallback", 0,
                    reason="chip-wedged", chip=str(device),
                    error=type(exc).__name__)
                break

    # -- dispatch ----------------------------------------------------------

    def _covers_for(self, ranges):
        if self._cover is None:
            return None
        return [self._cover[lo:hi] for lo, hi in ranges]

    def _start_on(self, s: int, device):
        chunks, ranges = self._shards[s]
        if _FAULT_HOOK is not None:
            _FAULT_HOOK(s, device)
        h = self._ck.upload_uniform_shard(chunks, self._covers_for(ranges),
                                          device=device)
        if self._stats is not None:
            self._stats.h2d_bytes += self._ck.shard_upload_nbytes(h)
        return self._ck.fused_uniform_shard_start(
            h, self._snapshots, self._bottommost)

    def _dispatch(self, s: int) -> None:
        while True:
            device = self._device_for(s)
            try:
                pending = self._start_on(s, device)
            except NotSupported:
                raise  # job-shape refusal: the caller's fallback ladder
            except Exception as e:
                if device is None:
                    raise  # even the default device failed: real error
                self._wedge(device, e)
                continue  # demote: next surviving chip / default device
            self._pend[s] = (pending, device)
            return

    def _fill(self) -> None:
        n = len(self._shards)
        while self._next < n and len(self._pend) < self._window:
            self._dispatch(self._next)
            self._next += 1

    # -- consume -----------------------------------------------------------

    def finish(self, s: int):
        """Block on shard s's result (order, zero_flags, cx_flags,
        has_complex); re-dispatches the shard on a surviving chip if its
        chip dies under the wait, then refills the window."""
        pending, device = self._pend.pop(s)
        while True:
            try:
                out = self._ck.fused_uniform_shard_finish(pending)
                if self._stats is not None:
                    self._stats.d2h_bytes += sum(
                        int(a.nbytes) for a in pending)
                break
            except Exception as e:
                if device is None:
                    raise
                self._wedge(device, e)
                self._dispatch(s)  # re-runs on a healthy chip, same bytes
                pending, device = self._pend.pop(s)
        # Callers time the blocking wait into stats.device_wait_usec and
        # the span `pipeline.merge_gc` around finish() itself; here that
        # span learns which chip the shard ran on.
        if self._plan is not None:
            sp = telemetry.current_span()
            if sp is not None:
                sp.tag(chip=str(device) if device is not None
                       else "default")
        self._fill()
        return out


def dispatch_shards(shards, cover, snapshots, bottommost, stats=None,
                    any_complex: bool = False, trace=None):
    """The single seam device_compaction.py calls: plan (knob + the
    eligibility matrix), then return (finish(s) callable, mesh_active).
    Ineligible/disabled jobs get the classic serial dispatch — every
    shard uploaded up front to the default device — so callers never
    branch on the mode."""
    plan = maybe_plan(shards, any_complex=any_complex, stats=stats,
                      trace=trace)
    run = MeshShardRun(plan, shards, cover, snapshots, bottommost,
                       stats=stats, trace=trace)
    return run.finish, plan is not None


def pipeline_devices(n_shards: int, stats=None, trace=None):
    """Chips for the pipelined plane's compute stage: the same gate as
    dispatch_shards minus the shard-shape checks (the pipeline validates
    uniformity itself, shard by shard, as scans land). Returns a device
    list (len >= 2) or None for the classic single-buffer path."""
    if not mesh_enabled() or n_shards < 2:
        return None
    try:
        devices = mesh_plan.mesh_devices()
    except Exception as e:
        _errors.swallow(reason="mesh-no-backend", exc=e)
        return None
    if len(devices) < 2:
        return None
    if stats is not None:
        stats.mesh_chips = len(devices)
        stats.mesh_shards = n_shards
    return devices
