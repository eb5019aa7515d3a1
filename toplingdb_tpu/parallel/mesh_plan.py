"""Shard→device planning shared by the mesh compaction execution mode
(ops/mesh_compaction.py) and the range weak-scaling probe
(parallel/scaling_probe.py).

One compaction job's uniform key-range shards (device_compaction's
`_prepare_uniform_shards` output) are placed round-robin over the range
axis of a (jobs=1, range=R) `jax.sharding.Mesh`; each shard's committed
uploads pin its fused merge+GC program to its chip, so the per-shard
kernels — and therefore the bytes they produce — are IDENTICAL to the
single-chip plane. Eligibility is decided here (one fallback matrix for
the execution mode, the probe, and the tests); measurement loops for the
probe/bench subprocesses live here too so the probe CLI stays thin.

Knobs: `TPULSM_MESH_DEVICES` caps how many chips a plan may use;
`TPULSM_MESH_MIN_ROWS` is the row floor below which fan-out overhead
would dominate (the enable knob `TPULSM_MESH_COMPACT` itself is read by
ops/mesh_compaction.py, keeping this module import-light).
"""

from __future__ import annotations

import os
import re
import time
from dataclasses import dataclass, field

# Probe exit codes: 0 = measured, EXIT_SKIP =
# environment cannot run the probe (missing backend, too few devices) —
# NOT a failure, the caller just drops the row; EXIT_FAILURE = the
# measurement itself broke.
EXIT_OK = 0
EXIT_FAILURE = 1
EXIT_SKIP = 3

# Below this many total survivor rows a mesh fan-out loses to dispatch +
# per-chip jit overhead; the job stays on one chip.
DEFAULT_MESH_MIN_ROWS = 1 << 18

# In-flight uploads per chip: 2 = classic double buffer (shard s+D's H2D
# streams while shard s computes on the same chip).
UPLOAD_DEPTH = 2


def configure_virtual_devices(n: int, platform: str = "cpu") -> None:
    """Rewrite env so the NEXT jax backend init exposes `n` virtual host
    devices. Must run before jax creates its backend — i.e. at subprocess
    entry (the probe) — because the device count is fixed at
    backend creation."""
    os.environ["JAX_PLATFORMS"] = platform
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()


def device_limit() -> int | None:
    """TPULSM_MESH_DEVICES: cap on chips a mesh plan may use (0/unset =
    every visible device)."""
    env = os.environ.get("TPULSM_MESH_DEVICES")
    if not env:
        return None
    try:
        n = int(env)
    except ValueError:
        return None
    return n if n > 0 else None


def mesh_min_rows() -> int:
    env = os.environ.get("TPULSM_MESH_MIN_ROWS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return DEFAULT_MESH_MIN_ROWS


def mesh_devices(limit: int | None = None) -> list:
    """The chips a mesh plan may schedule onto: jax.devices() of the
    default backend, capped by `limit` / TPULSM_MESH_DEVICES."""
    import jax

    devs = list(jax.devices())
    lim = limit if limit is not None else device_limit()
    if lim is not None:
        devs = devs[: max(1, lim)]
    return devs


def build_range_mesh(devices):
    """(jobs=1, range=R) Mesh over `devices` — the same topology the
    distributed-GC step and the weak-scaling probe use, so one mesh shape
    describes both the collective path and the per-chip shard path."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devices).reshape(1, len(devices)),
                ("jobs", "range"))


@dataclass
class MeshPlan:
    """One job's shard→chip placement. `assignments[s]` is the index into
    `devices` whose chip runs shard s; round-robin keeps each chip's queue
    ≤ ceil(S/D) deep and makes shard s and s+D the double-buffer pair."""

    devices: list
    assignments: list[int]
    total_rows: int
    depth: int = UPLOAD_DEPTH

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def window(self) -> int:
        """How many shards may be dispatched ahead of the consumer."""
        return self.depth * len(self.devices)


def check_eligibility(shards, any_complex: bool, devices,
                      min_rows: int | None = None):
    """The fallback matrix, one place: returns (reason, total_rows) with
    reason None when a mesh plan is allowed. Reasons (ARCHITECTURE.md
    §2.2.4): no-uniform-shards, single-shard, complex-groups,
    below-row-floor, single-device."""
    if not shards:
        return "no-uniform-shards", 0
    total = sum(int(c[2]) for chunks, _ranges in shards for c in chunks)
    if len(shards) < 2:
        return "single-shard", total
    if any_complex:
        # MERGE/SINGLE_DELETION groups fold host-side in stream order;
        # fanning the shards out buys nothing until the fold is sharded.
        return "complex-groups", total
    if total < (mesh_min_rows() if min_rows is None else min_rows):
        return "below-row-floor", total
    if len(devices) < 2:
        return "single-device", total
    return None, total


def plan_shards(shards, any_complex: bool = False, devices=None,
                min_rows: int | None = None):
    """(MeshPlan, None) when the job is mesh-eligible, (None, reason)
    otherwise. `shards` is device_compaction's `_prepare_uniform_shards`
    output (list of (chunks, row_ranges), or None when ineligible there)."""
    if devices is None:
        devices = mesh_devices()
    reason, total = check_eligibility(shards, any_complex, devices,
                                      min_rows)
    if reason is not None:
        return None, reason
    assignments = [s % len(devices) for s in range(len(shards))]
    return MeshPlan(list(devices), assignments, total), None


# ---------------------------------------------------------------------------
# Probe/bench measurement loops (subprocess side; jax imported lazily so
# configure_virtual_devices can run first)
# ---------------------------------------------------------------------------


def make_weak_scaling_job(n: int, seed: int = 7) -> dict:
    """Synthetic padded GC job of n rows for the distributed-GC step."""
    import numpy as np

    from toplingdb_tpu.db.dbformat import ValueType, make_internal_key
    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.ops.columnar import ColumnarEntries

    rng = np.random.default_rng(seed)
    draws = rng.integers(0, n, n)
    entries = [
        (make_internal_key(b"%012d" % draws[i], i + 1, ValueType.VALUE),
         b"v")
        for i in range(n)
    ]
    col = ColumnarEntries.from_entries(entries, 12)
    padded = ck.pad_columns(col)
    return {
        "key_words": np.asarray(padded["key_words"]),
        "key_len": np.asarray(padded["key_len"]),
        "inv_hi": np.asarray(padded["inv_hi"]),
        "inv_lo": np.asarray(padded["inv_lo"]),
        "vtype": np.asarray(padded["vtype"]),
        "w": padded["w"],
        "n": col.n,
    }


def weak_scaling_rows(rows_per_device: int, max_devices: int,
                      repeats: int = 3) -> list[dict]:
    """The probe's measurement loop: run_distributed_gc over a
    (jobs=1, range=R) mesh for R = 1,2,4..max_devices with a FIXED
    per-device row count; best-of-`repeats` wall per R."""
    import jax

    from toplingdb_tpu.parallel.distributed_gc import run_distributed_gc

    rows_list = []
    counts = [1 << i for i in range(max_devices.bit_length())
              if (1 << i) <= max_devices]
    for r in counts:
        n = rows_per_device * r
        job = make_weak_scaling_job(n)
        mesh = build_range_mesh(jax.devices()[:r])
        best = None
        for _ in range(repeats):
            t0 = time.time()
            run_distributed_gc(mesh, [job], [], True)
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        rows_list.append({"range_devices": r, "rows": n,
                          "rows_per_device": rows_per_device,
                          "best_s": round(best, 4),
                          "rows_per_s": round(n / best)})
    return rows_list


def _make_uniform_shards(n_shards: int, rows_per_shard: int,
                         key_len: int = 20, seed: int = 11):
    """Synthetic `_prepare_uniform_shards`-shaped input: n_shards range
    shards of presorted uniform internal keys (key_len includes the 8-byte
    trailer), disjoint user-key ranges so the stitched order is the
    concatenation — exactly the shard shape the mesh runner consumes."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = np.random.default_rng(seed)
    shards = []
    row_base = 0
    uk_len = key_len - 8
    for s in range(n_shards):
        uk = np.sort(rng.integers(0, rows_per_shard * 4, rows_per_shard))
        recs = []
        # Internal-key order: duplicate user keys need seq DESCENDING
        # within the run (the fused kernel's presorted precondition).
        j = rows_per_shard
        for k in uk:
            packed = ((row_base + j) << 8) | 1
            j -= 1
            recs.append((b"%02d" % s) + (b"%0*d" % (uk_len - 2, int(k)))
                        + packed.to_bytes(8, "little"))
        buf = np.frombuffer(b"".join(recs), np.uint8)
        chunk = ck.prepare_uniform_chunk(buf, rows_per_shard, key_len)
        shards.append(([chunk], [(row_base, row_base + rows_per_shard)]))
        row_base += rows_per_shard
    return shards


def mesh_compact_rows(rows_per_shard: int, max_devices: int,
                      repeats: int = 3, n_shards: int | None = None,
                      key_len: int = 20) -> list[dict]:
    """MEASURED mesh compaction rows (the MULTICHIP_r* dry-run promoted):
    run the SAME uniform shards through the mesh shard runner
    (ops/mesh_compaction.py) at 1 chip and at max_devices chips, wall and
    bytes/s per config. The shard set is fixed (strong scaling — one job
    fanned out), so rows_per_s ratio IS the mesh speedup."""
    import jax

    from toplingdb_tpu.ops import mesh_compaction as mc

    if n_shards is None:
        n_shards = max(2, max_devices) * UPLOAD_DEPTH
    shards = _make_uniform_shards(n_shards, rows_per_shard,
                                  key_len=key_len)
    total = n_shards * rows_per_shard
    out = []
    counts = sorted({1, min(max_devices, len(jax.devices()))})
    for r in counts:
        devices = jax.devices()[:r]
        plan, _reason = plan_shards(shards, devices=devices, min_rows=1)
        best = None
        for _ in range(repeats):
            t0 = time.time()
            if plan is None:  # r == 1: the serial single-chip twin
                run = mc.MeshShardRun(None, shards, None, [], True)
            else:
                run = mc.MeshShardRun(plan, shards, None, [], True)
            for s in range(len(shards)):
                run.finish(s)
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        out.append({"devices": r, "rows": total, "shards": n_shards,
                    "best_s": round(best, 4),
                    "rows_per_s": round(total / best),
                    "MBps": round(total * key_len / best / 1e6, 2)})
    return out
