"""Multichip probe CLI: a THIN wrapper over parallel/mesh_plan.py.

Two modes, both printing one JSON line:

  weak (default)  range-axis WEAK-SCALING of the distributed GC step:
                  `run_distributed_gc` over a (jobs=1, range=R) mesh for
                  R = 1,2,4..devices with a FIXED per-device row count —
                  the measured story for the all_to_all/ppermute
                  collective design (VERDICT r04 item 10).
  mesh            MEASURED mesh compaction: the same uniform key-range
                  shards through the mesh shard runner
                  (ops/mesh_compaction.py) at 1 chip vs all chips —
                  strong scaling of one fanned-out job.

On a CPU host the devices are virtual
(--xla_force_host_platform_device_count), so the numbers characterize
partitioning/dispatch overhead scaling, not chip throughput; the same
harness runs unchanged on a real multi-chip backend.

Runs in a SUBPROCESS (`python -m toplingdb_tpu.parallel.scaling_probe
...`) because the device count must be set before the jax backend
exists.

Exit codes: 0 measured; 3 SKIP (environment cannot run the probe — no
jax backend / too few devices; the caller drops the row); 1 the
measurement itself failed.
"""

from __future__ import annotations

import argparse
import json
import sys

from toplingdb_tpu.parallel import mesh_plan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("weak", "mesh"), default="weak")
    ap.add_argument("--rows-per-device", type=int, default=1 << 16)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    # Virtual CPU devices must be configured BEFORE the backend exists.
    mesh_plan.configure_virtual_devices(args.devices)
    try:
        import jax

        n_dev = len(jax.devices())
    except Exception as e:  # no usable backend: a skip, not a failure
        print(json.dumps({"skip": f"jax backend unavailable: {e!r}"[:200]}))
        return mesh_plan.EXIT_SKIP
    if n_dev < args.devices:
        print(json.dumps({"skip": f"{n_dev} devices < {args.devices} "
                                  "requested"}))
        return mesh_plan.EXIT_SKIP

    try:
        if args.mode == "mesh":
            rows = mesh_plan.mesh_compact_rows(
                args.rows_per_device, args.devices, args.repeats)
            print(json.dumps({"mesh_compact": rows}))
        else:
            rows = mesh_plan.weak_scaling_rows(
                args.rows_per_device, args.devices, args.repeats)
            print(json.dumps({"weak_scaling": rows}))
    except Exception as e:  # noqa: BLE001 — measurement broke
        print(json.dumps({"error": repr(e)[:300]}))
        return mesh_plan.EXIT_FAILURE
    return mesh_plan.EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
