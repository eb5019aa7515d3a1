"""Multi-chip parallelism: the framework's scale-out axes.

Two axes over a jax.sharding.Mesh, mirroring the reference's two parallelism
mechanisms (SURVEY.md §2.3):

  'jobs'   one compaction job per chip — the dcompact fan-out axis
           (reference: one CompactionJob per worker process). Jobs are
           independent: no collectives on the hot path.
  'range'  key-range sharding WITHIN one job — the subcompaction axis
           (reference GenSubcompactionBoundaries, compaction_job.cc:604-640),
           realized as a distributed sample-sort: local sort → splitter
           all_gather → all_to_all redistribution → local merge → boundary
           halo exchange (ppermute) for the GC mask.

distributed_gc.py implements the 'range' axis. The 'jobs' axis is the dcompact
service's chip pool (compaction/dcompact_service.py: `--chips N --workers N`
grants each job a chip of its own and hands the granted `jax.Device` down to
the job's uploads); mesh_plan.py places one job's shards over a grant of
several chips.
"""
