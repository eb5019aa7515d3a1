"""The control, and the faults of the tests: the dcompact service with one
guarantee of the configuration broken underneath it. Never started by a
benchmark run; `run.py --launcher faulty_service.py --launcher-arg ...`
puts it in the service's place (tests and the control runs only).

  --fault drop-row   each shard's result loses its last survivor where the
                     device path produces it (`fused_uniform_shard_finish`):
                     a compaction output lacks a row it must hold
  --fault drop-5pct  one survivor in twenty is lost: enough acknowledged
                     writes vanish for a sample of reads to meet some
  --fault drop-row-early
                     drop-row in a job cell's first five timed runs only
                     (job directories r001..r005): runs whose output the
                     reference never reads, because a newer run of the same
                     job is the one kept for it
  --fault leave-pipeline
                     every job is refused by the pipelined data plane and
                     takes the serial program: right answers, off the path
                     the cell measures (`jobs_left_pipeline`)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import span_service  # noqa: E402


def plant(fault: str) -> None:
    if fault == "leave-pipeline":
        from toplingdb_tpu.ops import pipeline

        def refusing_run_pipelined(*args, **kw):
            raise pipeline.PipelineIneligible("refused by the fault")

        pipeline.run_pipelined = refusing_run_pipelined  # looked up per job
        return

    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    from toplingdb_tpu.compaction import worker

    finish = ck.fused_uniform_shard_finish
    run_job = worker.run_job
    early = [False]

    def noting_run_job(job_dir):
        early[0] = os.path.basename(job_dir) in {
            f"r{r:03d}" for r in range(1, 6)}
        return run_job(job_dir)

    worker.run_job = noting_run_job  # the service looks it up per job

    def faulty_finish(pending):
        order, zero, cx, has_complex = finish(pending)
        if fault == "drop-row" or (fault == "drop-row-early" and early[0]):
            keep = np.ones(len(order), dtype=bool)
            keep[-1:] = False
        elif fault == "drop-row-early":
            keep = np.ones(len(order), dtype=bool)
        elif fault == "drop-5pct":
            keep = np.arange(len(order)) % 20 != 7
        else:
            raise SystemExit(f"unknown fault {fault!r}")
        return order[keep], zero[keep], cx[keep], has_complex

    ck.fused_uniform_shard_finish = faulty_finish


def main() -> int:
    _svc, rest = span_service.build_service(sys.argv[1:])
    if len(rest) != 2 or rest[0] != "--fault":
        raise SystemExit("usage: faulty_service.py <service options> "
                         "--fault drop-row|drop-5pct|drop-row-early|"
                         "leave-pipeline")
    plant(rest[1])
    span_service.serve_commands({})
    return 0


if __name__ == "__main__":
    sys.exit(main())
