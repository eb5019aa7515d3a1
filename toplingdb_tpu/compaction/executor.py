"""The distributed-compaction executor boundary.

The serializable seam of the framework, modeled on the reference's
CompactionExecutor plugin API (db/compaction/compaction_executor.h:160-178 in
/root/reference):

  CompactionExecutorFactory.should_run_local / allow_fallback_to_local /
  new_executor — decide routing per job;
  CompactionExecutor.execute(db, compaction, snapshots, alloc) — run the data
  plane somewhere else and return (outputs, stats).

Three executors:
  DeviceCompactionExecutor      in-process JAX data plane (device="tpu", or
                                "cpu-jax" for XLA:CPU in tests) — the TPU
                                analogue of a same-host dcompact worker
                                with HBM DMA instead of NFS. The DB process
                                then holds the chip.
  SubprocessCompactionExecutor  full process boundary: CompactionParams
                                serialized to a job dir, a worker process
                                (toplingdb_tpu.compaction.worker) executes
                                and writes CompactionResults; outputs are
                                renamed into the DB dir (reference
                                CompactionJob::RunRemote,
                                compaction_job.cc:921-1152).
  (cluster fan-out over a TPU pod lives in toplingdb_tpu/parallel.)
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import subprocess
import sys
import time

from toplingdb_tpu.compaction.compaction_job import CompactionStats
from toplingdb_tpu.compaction.picker import Compaction
from toplingdb_tpu.db import filename
from toplingdb_tpu.utils.table_properties_collector import (
    serialize_collector_factory,
)
from toplingdb_tpu.db.version_edit import FileMetaData
from toplingdb_tpu.utils.status import Corruption, IOError_, NotSupported


def _telemetry():
    from toplingdb_tpu.utils import telemetry

    return telemetry


def _store_spec_of(env) -> str | None:
    """Serializable store spec a worker process can reopen: the HTTP URL
    of a StoreClient or the root path of a LocalObjectStore. None when
    the env has no store or its backend has no process-portable name."""
    store = getattr(env, "store", None)
    if store is None:
        return None
    url = getattr(store, "url", None)
    if isinstance(url, str) and url:
        return url
    root = getattr(store, "root", None)
    return root if isinstance(root, str) and root else None


class CompactionExecutor:
    def execute(self, db, compaction: Compaction, snapshots: list[int],
                new_file_number) -> tuple[list[FileMetaData], CompactionStats]:
        raise NotImplementedError

    def clean_files(self) -> None:
        pass


class CompactionExecutorFactory:
    """Reference CompactionExecutorFactory (compaction_executor.h:170-178)."""

    def should_run_local(self, compaction: Compaction) -> bool:
        return False

    def allow_fallback_to_local(self) -> bool:
        return True

    def new_executor(self, compaction: Compaction) -> CompactionExecutor:
        raise NotImplementedError

    def job_url(self, job_id: int, attempt: int) -> str:
        return ""


# ---------------------------------------------------------------------------
# In-process device executor
# ---------------------------------------------------------------------------


class DeviceCompactionExecutor(CompactionExecutor):
    def __init__(self, device: str = "tpu"):
        self.device = device

    def execute(self, db, compaction, snapshots, new_file_number):
        from toplingdb_tpu.db.blob import maybe_new_blob_gc
        from toplingdb_tpu.ops.device_compaction import run_device_compaction

        return run_device_compaction(
            db.env, db.dbname, db.icmp, compaction, db.table_cache,
            db.options.table_options_for_level(
                compaction.output_level, compaction.bottommost),
            snapshots,
            merge_operator=db.options.merge_operator,
            compaction_filter=db.options.compaction_filter,
            new_file_number=new_file_number,
            device_name=self.device,
            blob_resolver=db.blob_source.get,
            blob_gc=maybe_new_blob_gc(db, compaction, new_file_number),
            column_family=(compaction.cf_id, db.cf_name(compaction.cf_id)),
        )


class DeviceCompactionExecutorFactory(CompactionExecutorFactory):
    """Route compactions at/below `min_input_bytes` to the local CPU path and
    the rest to the device data plane (small jobs aren't worth the transfer —
    the same policy ShouldRunLocal expresses in the reference)."""

    def __init__(self, device: str = "tpu", min_input_bytes: int = 0,
                 allow_fallback: bool = True):
        self.device = device
        self.min_input_bytes = min_input_bytes
        self._allow_fallback = allow_fallback

    def should_run_local(self, compaction: Compaction) -> bool:
        return compaction.total_input_bytes() < self.min_input_bytes

    def allow_fallback_to_local(self) -> bool:
        return self._allow_fallback

    def new_executor(self, compaction: Compaction) -> CompactionExecutor:
        return DeviceCompactionExecutor(self.device)


# ---------------------------------------------------------------------------
# Serialized job boundary (dcompact analogue)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompactionParams:
    """Everything a worker needs to run one compaction job — the analogue of
    the reference's CompactionParams (compaction_executor.h:33-118). Plugin
    objects travel as registry names (ObjectRpcParam.clazz analogue)."""

    job_id: int
    attempt: int
    dbname: str                      # source DB dir (shared storage)
    output_dir: str                  # where the worker writes SSTs
    input_files: list[str]           # absolute SST paths
    output_level: int
    bottommost: bool
    max_output_file_size: int
    snapshots: list[int]
    comparator: str                  # registry name
    merge_operator: str | None       # registry name
    compaction_filter: str | None    # registry name
    compression: int
    block_size: int
    creation_time: int
    table_format: str = "block"
    # What else shapes a SingleFastTable: its hash index, and the filter
    # policy's registry name ("" = no filter; None = not sent, the worker
    # keeps TableOptions' default — a job an older DB wrote).
    hash_index: bool = False
    filter_policy: str | None = None
    # SliceTransform serialized name (utils/slice_transform.py) or None —
    # required when table_format == 'plain' (prefix hash index) and feeds
    # prefix blooms for the other formats.
    prefix_extractor: str | None = None
    # Job-lease duration: the worker heartbeats job_dir/heartbeat at
    # ~lease_sec/3; a heartbeat older than lease_sec marks the job
    # orphaned (compaction/resilience.py). 0 disables heartbeating.
    lease_sec: float = 30.0
    smallest_seqno_guard: int = 0
    device: str = "cpu"
    cf_id: int = 0
    cf_name: str = "default"
    collectors: list = dataclasses.field(default_factory=list)
    # Propagated trace context (utils/telemetry.py inject()): the worker
    # adopts it, records its spans locally, and returns them in
    # results.json so the DB stitches one end-to-end trace. None = the
    # submitting op was untraced.
    trace: dict | None = None
    # Disaggregated-storage mode (toplingdb_tpu/storage/): when set, the
    # worker resolves inputs by content address from the shared store
    # (input_addrs pairs with input_files) and publishes outputs back —
    # ZERO SST bytes cross the job transport. None = classic path mode.
    store_spec: str | None = None
    input_addrs: list | None = None
    checksum_func: str | None = None  # output stamping func in store mode

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1)

    @staticmethod
    def from_json(s: str) -> "CompactionParams":
        return CompactionParams(**json.loads(s))


@dataclasses.dataclass
class CompactionResults:
    """Worker → DB results (reference CompactionResults,
    compaction_executor.h:120-158)."""

    status: str                      # "ok" | error text
    output_files: list[dict]         # serialized FileMetaData (paths relative)
    stats: dict
    curl_time_usec: int = 0          # kept for parity with reference fields
    work_time_usec: int = 0
    # Worker-side finished span dicts (telemetry plane): the DB side
    # attaches them to the originating trace (attach_remote).
    spans: list = dataclasses.field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1)

    @staticmethod
    def from_json(s: str) -> "CompactionResults":
        return CompactionResults(**json.loads(s))


def encode_file_meta(meta: FileMetaData, path: str) -> dict:
    return {
        "path": path,
        "file_size": meta.file_size,
        "smallest": meta.smallest.hex(),
        "largest": meta.largest.hex(),
        "smallest_seqno": meta.smallest_seqno,
        "largest_seqno": meta.largest_seqno,
        "num_entries": meta.num_entries,
        "num_deletions": meta.num_deletions,
        "num_range_deletions": meta.num_range_deletions,
        "blob_refs": list(meta.blob_refs),
        "marked_for_compaction": meta.marked_for_compaction,
    }


def decode_file_meta(d: dict, number: int) -> FileMetaData:
    return FileMetaData(
        number=number,
        file_size=d["file_size"],
        smallest=bytes.fromhex(d["smallest"]),
        largest=bytes.fromhex(d["largest"]),
        smallest_seqno=d["smallest_seqno"],
        largest_seqno=d["largest_seqno"],
        num_entries=d["num_entries"],
        num_deletions=d["num_deletions"],
        num_range_deletions=d["num_range_deletions"],
        blob_refs=list(d.get("blob_refs", [])),
        marked_for_compaction=d.get("marked_for_compaction", False),
        # Store-mode outputs arrive pre-stamped (the worker checksummed
        # them for their content address) — the install path's
        # stamp_file_checksum sees the digest and skips the re-read.
        file_checksum=bytes.fromhex(d["file_checksum"])
        if d.get("file_checksum") else b"",
        file_checksum_func_name=d.get("file_checksum_func_name", ""),
    )


_job_counter = itertools.count(1)


class SubprocessCompactionExecutor(CompactionExecutor):
    """Ship the job to a worker process through a shared job dir — the
    transport shape of dcompact (HTTP+NFS in the reference; a local spawn +
    shared filesystem here; the RPC hop is pluggable via `spawn`)."""

    def __init__(self, device: str = "cpu", job_root: str | None = None,
                 spawn=None, policy=None, fault_injector=None):
        self.device = device
        self.job_root = job_root
        self._local_spawn = spawn is None
        self.spawn = spawn or self._spawn_local
        self._job_seq = 0
        # Set by the retry driver (compaction/resilience.py) before each
        # execute(); attempt N gets its own att-NN dir so a failed
        # attempt's partial outputs never collide with the retry's.
        self.attempt = 0
        self.policy = policy          # DcompactOptions or None (defaults)
        self.fault_injector = fault_injector
        self.url = ""                 # transport identity (HTTP sets it)
        self._plan = None             # active injected-fault plan

    def _spawn_local(self, job_dir: str, device: str) -> None:
        if device == "tpu" and "jax" in sys.modules:
            # A chip belongs to one process: a parent that has touched
            # JAX holds it, and the worker would fail or hang at backend
            # start-up. The DB process of a device deployment stays off
            # JAX (or runs the in-process DeviceCompactionExecutor).
            raise NotSupported(
                "cannot spawn a device='tpu' worker from a process that "
                "has imported jax: it would hold the chip the worker needs")
        env = dict(os.environ)
        if device == "cpu":
            env.setdefault("JAX_PLATFORMS", "cpu")
        if self._plan == "kill":
            # The worker crashes hard mid-job (os._exit after heartbeats +
            # partial output) — deterministically a kill -9.
            env["TPULSM_TEST_WORKER_CRASH"] = "mid_job"
        timeout = (self.policy.attempt_timeout
                   if self.policy is not None else 3600.0)
        r = subprocess.run(
            [sys.executable, "-m", "toplingdb_tpu.compaction.worker",
             "--job-dir", job_dir],
            capture_output=True, env=env, timeout=timeout,
        )
        if r.returncode != 0:
            raise IOError_(
                f"compaction worker failed rc={r.returncode}: "
                f"{r.stderr.decode(errors='replace')[-2000:]}"
            )

    def execute(self, db, compaction, snapshots, new_file_number):
        # Job ids come from a PROCESS-WIDE counter: the factory builds one
        # executor per compaction, and concurrent jobs with per-executor
        # counters collided on the same job dir (each deleting the
        # other's params/results mid-flight).
        self._job_seq = next(_job_counter)
        job_root = self.job_root or os.path.join(db.dbname, "dcompact")
        job_dir = os.path.join(
            job_root, f"job-{self._job_seq:05d}", f"att-{self.attempt:02d}"
        )
        os.makedirs(os.path.join(job_dir, "out"), exist_ok=True)
        try:
            return self._execute_in(db, compaction, snapshots,
                                    new_file_number, job_dir)
        except BaseException:
            # Sweep THIS attempt's partial state (params, lease, partial
            # outputs) so a retry or the on-open orphan sweep never sees
            # half-written SSTs as live job state.
            import shutil as _sh

            _sh.rmtree(job_dir, ignore_errors=True)
            self._rmdir_if_empty(os.path.dirname(job_dir))
            raise

    def _execute_in(self, db, compaction, snapshots, new_file_number,
                    job_dir):
        opts = db.options
        if opts.compaction_filter is not None:
            # Unregistered filters can't travel the serialized boundary;
            # raising here triggers fallback-to-local in the scheduler.
            from toplingdb_tpu.utils.compaction_filter import (
                create_compaction_filter,
            )

            create_compaction_filter(opts.compaction_filter.name())
        policy = self.policy
        if policy is None:
            policy = getattr(db.options, "dcompact", None)
        lease_sec = policy.lease_sec if policy is not None else 30.0
        # Store mode: when the DB runs on a SharedSstEnv and EVERY input
        # carries a checksum address, the worker pulls inputs from the
        # store and publishes outputs back — the job dir ships only
        # metadata. One unstamped input (pre-upgrade file) falls back to
        # path mode for the whole job.
        store_spec, input_addrs = None, None
        if hasattr(db.env, "publish_sst"):
            from toplingdb_tpu.storage.object_store import address_of_meta

            addrs = [address_of_meta(f) for _, f in compaction.all_inputs()]
            spec = _store_spec_of(db.env)
            if spec is not None and all(a is not None for a in addrs):
                store_spec, input_addrs = spec, addrs
        params = CompactionParams(
            job_id=self._job_seq,
            attempt=self.attempt,
            dbname=db.dbname,
            output_dir=os.path.join(job_dir, "out"),
            input_files=[
                filename.table_file_name(db.dbname, f.number)
                for _, f in compaction.all_inputs()
            ],
            output_level=compaction.output_level,
            bottommost=compaction.bottommost,
            max_output_file_size=compaction.max_output_file_size,
            snapshots=list(snapshots),
            comparator=opts.comparator.name(),
            merge_operator=(
                opts.merge_operator.name() if opts.merge_operator else None
            ),
            compaction_filter=(
                opts.compaction_filter.name() if opts.compaction_filter else None
            ),
            compression=opts.compression_for_level(
                compaction.output_level, compaction.bottommost),
            block_size=opts.table_options.block_size,
            creation_time=int(time.time()),
            device=self.device,
            table_format=opts.table_options_for_level(
                compaction.output_level, compaction.bottommost).format,
            hash_index=opts.table_options.hash_index,
            filter_policy=(opts.table_options.filter_policy.name()
                           if opts.table_options.filter_policy else ""),
            prefix_extractor=(
                opts.table_options.prefix_extractor.name()
                if getattr(opts.table_options, "prefix_extractor", None)
                else None
            ),
            cf_id=compaction.cf_id,
            cf_name=db.cf_name(compaction.cf_id),
            collectors=[
                serialize_collector_factory(f)
                for f in opts.table_options.properties_collector_factories
            ],
            lease_sec=lease_sec,
            trace=_telemetry().inject(),
            store_spec=store_spec,
            input_addrs=input_addrs,
            checksum_func=(opts.file_checksum or "crc32c")
            if store_spec else None,
        )
        with open(os.path.join(job_dir, "params.json"), "w") as f:
            f.write(params.to_json())
        from toplingdb_tpu.compaction.resilience import write_lease

        write_lease(job_dir, self._job_seq, self.attempt, lease_sec)
        inj = self.fault_injector
        self._plan = inj.plan(self._job_seq, self.attempt) if inj else None
        t0 = time.time()
        if inj is not None:
            inj.before_spawn(self._plan)
        if self._plan == "kill" and not self._local_spawn:
            # Non-subprocess transports can't kill a real worker process;
            # simulate the observable state of one: heartbeats + a partial
            # output exist, then the connection dies.
            with open(os.path.join(job_dir, "out", "partial.sst"), "wb") as f:
                f.write(b"\x00" * 64)
            raise IOError_("injected: worker killed mid-job")
        self.spawn(job_dir, self.device)
        if inj is not None:
            inj.after_spawn(self._plan, job_dir)
        rpc_usec = int((time.time() - t0) * 1e6)
        try:
            with open(os.path.join(job_dir, "results.json")) as f:
                results = CompactionResults.from_json(f.read())
        except (OSError, ValueError, TypeError) as e:
            # Missing/truncated/garbage results.json: a worker crash
            # between compute and a complete write — a transport failure,
            # not DB corruption.
            raise IOError_(f"dcompact results unreadable: {e!r}") from e
        if results.status != "ok":
            raise IOError_(f"worker error: {results.status}")
        if results.spans:
            # Stitch the worker's spans into the compaction trace active
            # on this thread (no-op when the job ran untraced).
            _telemetry().attach_current(results.spans)
        # Rename outputs into the DB dir under fresh file numbers
        # (reference RunRemote rename loop, compaction_job.cc:1019-1073).
        # Store-mode outputs ADOPT instead: the bytes live in the shared
        # store under their content address; only the reference lands here.
        outputs = []
        shipped = 0
        for d in results.output_files:
            num = new_file_number()
            dst = filename.table_file_name(db.dbname, num)
            addr = d.get("store_addr")
            if addr and hasattr(db.env, "adopt"):
                db.env.adopt(dst, addr)
                try:
                    db.env.store.unpin(addr)  # ref now shields it from GC
                except Exception as e:  # noqa: BLE001
                    from toplingdb_tpu.utils import errors as _errors

                    _errors.swallow(reason="dcompact-adopt-unpin", exc=e)
            else:
                os.replace(os.path.join(params.output_dir, d["path"]), dst)
                shipped += int(d["file_size"])
            outputs.append(decode_file_meta(d, num))
        # stats.device stays what the worker ran on, not what was asked.
        stats = CompactionStats(**results.stats)
        stats.sst_bytes_shipped = shipped
        stats.remote = True
        stats.work_time_usec = results.work_time_usec
        # Transport time, the analogue of the reference's curl_time_usec.
        stats.rpc_time_usec = rpc_usec - results.work_time_usec
        self._cleanup(job_dir)
        return outputs, stats

    @staticmethod
    def _rmdir_if_empty(path: str) -> None:
        try:
            if os.path.isdir(path) and not os.listdir(path):
                os.rmdir(path)
        except OSError:
            pass

    @classmethod
    def _cleanup(cls, job_dir: str) -> None:
        """Remove the whole attempt dir (outputs were renamed into the DB
        dir) and the job skeleton if this was its last attempt — a
        successful job leaves NO residue for the on-open orphan sweep."""
        import shutil as _sh

        _sh.rmtree(job_dir, ignore_errors=True)
        cls._rmdir_if_empty(os.path.dirname(job_dir))


class SubprocessCompactionExecutorFactory(CompactionExecutorFactory):
    def __init__(self, device: str = "cpu", allow_fallback: bool = True,
                 min_input_bytes: int = 0, job_root: str | None = None,
                 policy=None, fault_injector=None):
        self.device = device
        self._allow_fallback = allow_fallback
        self.min_input_bytes = min_input_bytes
        self.job_root = job_root
        self.policy = policy                  # DcompactOptions or None
        self.fault_injector = fault_injector  # DcompactFaultInjector

    def should_run_local(self, compaction: Compaction) -> bool:
        return compaction.total_input_bytes() < self.min_input_bytes

    def allow_fallback_to_local(self) -> bool:
        return self._allow_fallback

    def new_executor(self, compaction: Compaction) -> CompactionExecutor:
        return SubprocessCompactionExecutor(
            self.device, self.job_root, policy=self.policy,
            fault_injector=self.fault_injector)

    def job_url(self, job_id: int, attempt: int) -> str:
        return f"file://{self.job_root or 'dcompact'}/job-{job_id:05d}/att-{attempt:02d}"
