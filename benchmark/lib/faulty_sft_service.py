"""The control of the SingleFastTable deployment's cell: the dcompact
service with the scan of a SingleFastTable input broken underneath it.
Never started by a benchmark run; `run.py --launcher faulty_sft_service.py
--launcher-arg --fault --launcher-arg drop-every-16th` puts it in the
service's place (the tests and the control runs only).

  --fault drop-every-16th   the scan of a single_fast input leaves out
                            every 16th entry of the file (ordinal % 16 ==
                            15) and hands the one before it twice: the
                            plan's totals still agree, and every such row
                            is missing from the job's output
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import span_service  # noqa: E402


def plant(fault: str) -> None:
    if fault != "drop-every-16th":
        raise SystemExit(f"unknown fault {fault!r}")
    import numpy as np

    from toplingdb_tpu.table.single_fast import SingleFastTableReader

    scan = SingleFastTableReader.scan_into

    def faulty_scan(self, e0, e1, kv, row0, k0, v0, k_cap, v_cap):
        used = scan(self, e0, e1, kv, row0, k0, v0, k_cap, v_cap)
        lost = np.flatnonzero(np.arange(e0, e1) % 16 == 15)
        lost = lost[lost > 0] + row0
        same = ((kv.key_lens[lost] == kv.key_lens[lost - 1])
                & (kv.val_lens[lost] == kv.val_lens[lost - 1]))
        lost = lost[same]
        for buf, off, ln in ((kv.key_buf, kv.key_offs, kv.key_lens),
                             (kv.val_buf, kv.val_offs, kv.val_lens)):
            width = int(ln[lost].max()) if len(lost) else 0
            col = np.arange(width)[None, :]
            if v0 is None and buf is kv.val_buf:
                # Values referenced into the file's image (read-only): the
                # row points at its neighbour's value instead.
                off[lost] = off[lost - 1]
                continue
            buf[off[lost][:, None] + col] = buf[off[lost - 1][:, None] + col]
        return used

    SingleFastTableReader.scan_into = faulty_scan  # looked up per call


def main() -> int:
    _svc, rest = span_service.build_service(sys.argv[1:])
    if len(rest) != 2 or rest[0] != "--fault":
        raise SystemExit("usage: faulty_sft_service.py <service options> "
                         "--fault drop-every-16th")
    plant(rest[1])
    span_service.serve_commands({})
    return 0


if __name__ == "__main__":
    sys.exit(main())
