"""The control of the merge deployment's cell: the dcompact service with
the columnar fold broken underneath it. Never started by a benchmark run;
`run.py --launcher faulty_merge_service.py --launcher-arg --fault
--launcher-arg drop-operand` puts it in the service's place (the tests and
the control runs only).

  --fault drop-operand   the fold of every chain of two or more values
                         leaves out the oldest of them (an operand, or the
                         base the chain ends on): every such key's sum is
                         short by one write of the seed
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import span_service  # noqa: E402


def plant(fault: str) -> None:
    if fault != "drop-operand":
        raise SystemExit(f"unknown fault {fault!r}")
    import numpy as np

    from toplingdb_tpu.utils import merge_operator as mo

    def dropping_reduce(values, starts):
        sums = np.add.reduceat(values, starts)
        ends = np.append(starts[1:], len(values))
        many = ends - starts > 1
        sums[many] -= values[ends[many] - 1]
        return sums

    def columnar_fold(self):
        return mo.ColumnarFold(8, "<u8", dropping_reduce)

    mo.UInt64AddOperator.columnar_fold = columnar_fold  # asked per job


def main() -> int:
    _svc, rest = span_service.build_service(sys.argv[1:])
    if len(rest) != 2 or rest[0] != "--fault":
        raise SystemExit("usage: faulty_merge_service.py <service options> "
                         "--fault drop-operand")
    plant(rest[1])
    span_service.serve_commands({})
    return 0


if __name__ == "__main__":
    sys.exit(main())
