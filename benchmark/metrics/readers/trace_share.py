"""A quantity of the traced window's summary (`lib/span_reduce.reduce`):
the sum of entries of one of its tables, over the time inside jobs (as a
share, times `scale`) or over the million input rows (`per_Mrow`, in ms).

`table` names a table of the summary (`device_s_by_scope`, `span_self_s`,
`gap_totals_s`), `keys` its entries; a key that ends in `*` takes every
entry with that prefix. `device` says that the number is a reading of the
device: a rehearsal's XLA:CPU threads are none, and it gives nothing.
Nothing to read (no trace, no such table, none of the entries) gives
nothing."""


def read(facts, table, keys, per_Mrow=False, scale=100.0, device=False):
    trace = facts.get("trace")
    if not trace or (device and facts.get("rehearsal")):
        return None
    entries = trace.get(table)
    if not entries:
        return None
    found = [v for k, v in entries.items() for want in keys
             if k == want or (want.endswith("*") and k.startswith(want[:-1]))]
    if not found:
        return None
    if per_Mrow:
        if not facts.get("rows_in"):
            return None
        return sum(found) * 1e3 / (facts["rows_in"] / 1e6)
    if not trace.get("job_s"):
        return None
    return scale * sum(found) / trace["job_s"]
