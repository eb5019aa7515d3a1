"""The plain reference of one compaction job, and the comparison with what
the service wrote. numpy and `sst_plain` only; nothing of the package.

Semantics (the deployment's: puts only, no snapshot held, bytewise keys):
of all input rows with one user key the row with the highest sequence
survives, with its value untouched; the output is in key order; at the
bottommost level a survivor's sequence is zeroed (nothing older can exist
below it). Every input row must also be a write of this run's seed.
"""

from __future__ import annotations

import json
import os

import numpy as np

from . import sst_plain


def _read_all(paths):
    keys, vals = [], []
    for p in paths:
        k, v = sst_plain.read_rows(p)
        if len(k):
            keys.append(k)
            vals.append(v)
    ikeys = np.concatenate(keys)
    return sst_plain.split_internal(ikeys) + (np.concatenate(vals),)


def expected_output(ukey, seq, vtype, vals, bottommost: bool):
    """Survivors of the input rows, in output order."""
    order = np.lexsort((np.iinfo(np.uint64).max - seq, ukey))
    ukey, seq, vtype, vals = ukey[order], seq[order], vtype[order], vals[order]
    first = np.ones(len(ukey), dtype=bool)
    first[1:] = ukey[1:] != ukey[:-1]
    seq = seq[first]
    if bottommost:
        seq = np.zeros_like(seq)
    return ukey[first], seq, vtype[first], vals[first]


def compare_job(job_dir: str, workload) -> dict:
    """Counts for one finished job dir (params.json, results.json, out/):
    rows of the output that differ from the reference's (missing and extra
    rows included), and input rows no write of the seed made."""
    with open(os.path.join(job_dir, "params.json")) as f:
        params = json.load(f)
    with open(os.path.join(job_dir, "results.json")) as f:
        results = json.load(f)
    iu, iseq, ity, ival = _read_all(params["input_files"])
    eu, eseq, ety, eval_ = expected_output(
        iu, iseq, ity, ival, bool(params["bottommost"]))
    outs = [os.path.join(job_dir, "out", d["path"])
            for d in results["output_files"]]
    ou, oseq, oty, oval = _read_all(outs)
    n = min(len(eu), len(ou))
    wrong = abs(len(eu) - len(ou))
    if n:
        same = ((eu[:n] == ou[:n]) & (eseq[:n] == oseq[:n])
                & (ety[:n] == oty[:n])
                & (eval_[:n] == oval[:n]).all(axis=1))
        wrong += int((~same).sum())
    return {
        "rows_in": int(len(iu)), "rows_out": int(len(ou)),
        "rows_expected": int(len(eu)), "rows_wrong": wrong,
        "rows_not_from_seed": workload.rows_not_from_seed(iu, ival),
        "records_misreported": int(
            results["stats"]["input_records"] != len(iu))
        + int(results["stats"]["output_records"] != len(ou)),
    }
