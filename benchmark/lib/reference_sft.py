"""The plain reference of one compaction job of the SingleFastTable
deployment: inputs and outputs may be SingleFastTables (`sft_plain`) and
block tables (`sst_plain`), in any mix. The semantics are `reference.py`'s
(puts only, no snapshot held, bytewise keys: the newest version of every
user key, in key order, values untouched, the sequence zeroed at the
bottommost level); only the reading differs. numpy, `sst_plain`,
`sft_plain`; nothing of the package.
"""

from __future__ import annotations

import json
import os

import numpy as np

from . import sft_plain, sst_plain
from .reference import expected_output


def read_rows(path: str):
    """([m, K] internal keys, [m, V] values) of one SST of either format."""
    if sft_plain.is_single_fast_table(path):
        return sft_plain.read_rows(path)
    return sst_plain.read_rows(path)


def _read_all(paths):
    """(user key numbers, sequences, types, values, SingleFastTables among
    them, rows read from SingleFastTables)."""
    keys, vals = [], []
    sfts = sft_rows = 0
    for p in paths:
        k, v = read_rows(p)
        if sft_plain.is_single_fast_table(p):
            sfts += 1
            sft_rows += len(k)
        if len(k):
            keys.append(k)
            vals.append(v)
    if not keys:
        z = np.zeros(0, np.uint64)
        return z, z, np.zeros(0, np.uint8), np.zeros((0, 0), np.uint8), \
            sfts, sft_rows
    return sst_plain.split_internal(np.concatenate(keys)) + (
        np.concatenate(vals), sfts, sft_rows)


def compare_job(job_dir: str, workload) -> dict:
    """Counts for one finished job dir (params.json, results.json, out/):
    rows of the output that differ from the reference's (missing and extra
    rows included), input rows no write of the seed made, outputs that are
    not SingleFastTables, and what the job's reply said of its rows
    against what the files hold."""
    with open(os.path.join(job_dir, "params.json")) as f:
        params = json.load(f)
    with open(os.path.join(job_dir, "results.json")) as f:
        results = json.load(f)
    iu, iseq, ity, ival, sfts_in, sft_rows_in = _read_all(
        params["input_files"])
    eu, eseq, ety, eval_ = expected_output(
        iu, iseq, ity, ival, bool(params["bottommost"]))
    outs = [os.path.join(job_dir, "out", d["path"])
            for d in results["output_files"]]
    ou, oseq, oty, oval, sfts_out, sft_rows_out = _read_all(outs)
    n = min(len(eu), len(ou))
    wrong = abs(len(eu) - len(ou))
    if n:
        same = ((eu[:n] == ou[:n]) & (eseq[:n] == oseq[:n])
                & (ety[:n] == oty[:n])
                & (eval_[:n] == oval[:n]).all(axis=1))
        wrong += int((~same).sum())
    stats = results["stats"]
    return {
        "rows_in": int(len(iu)), "rows_out": int(len(ou)),
        "rows_expected": int(len(eu)), "rows_wrong": wrong,
        "sft_inputs": sfts_in, "sft_rows_in": sft_rows_in,
        "outputs": len(outs),
        "outputs_not_single_fast": len(outs) - sfts_out,
        "rows_not_from_seed": workload.rows_not_from_seed(iu, ival),
        "records_misreported": int(stats["input_records"] != len(iu))
        + int(stats["output_records"] != len(ou))
        + int(stats.get("sft_input_rows") != sft_rows_in)
        + int(stats.get("sft_output_rows") != sft_rows_out),
    }
