"""The control of the cold-level deployment's cell: the dcompact service
with the scan of a ZipTable input broken underneath it. Never started by a
benchmark run; `run.py --launcher faulty_zip_service.py --launcher-arg
--fault --launcher-arg drop-group-tail` puts it in the service's place (the
tests and the control runs only).

  --fault drop-group-tail   the scan of a zip input leaves out the last
                            entry of every key group (a range of 16
                            entries) and hands the one before it twice:
                            the plan's totals still agree, and every such
                            key is missing from the job's output
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import span_service  # noqa: E402


def plant(fault: str) -> None:
    if fault != "drop-group-tail":
        raise SystemExit(f"unknown fault {fault!r}")
    import numpy as np

    from toplingdb_tpu.table.zip_table import ZipTableReader

    scan = ZipTableReader.scan_columnar

    def faulty_scan(self, e0, e1):
        kb, ko, kl, vb, vo, vl = scan(self, e0, e1)
        e0 = max(0, int(e0))
        lost = np.flatnonzero(
            (np.arange(e0, e0 + len(ko)) % self.G == self.G - 1))
        lost = lost[lost > 0]
        same = (kl[lost] == kl[lost - 1]) & (vl[lost] == vl[lost - 1])
        lost = lost[same]
        for buf, off, ln in ((kb, ko, kl), (vb, vo, vl)):
            width = int(ln[lost].max()) if len(lost) else 0
            col = np.arange(width)[None, :]
            buf[off[lost][:, None] + col] = buf[off[lost - 1][:, None] + col]
        return kb, ko, kl, vb, vo, vl

    ZipTableReader.scan_columnar = faulty_scan  # looked up per call


def main() -> int:
    _svc, rest = span_service.build_service(sys.argv[1:])
    if len(rest) != 2 or rest[0] != "--fault":
        raise SystemExit("usage: faulty_zip_service.py <service options> "
                         "--fault drop-group-tail")
    plant(rest[1])
    span_service.serve_commands({})
    return 0


if __name__ == "__main__":
    sys.exit(main())
