"""Device time under the job annotations of the trace (chip-seconds, all
device work between a job's start and end, whatever program did it), per
million input rows, or as the share of it that the chip's memory bandwidth
alone would have needed for the jobs' algorithmic bytes."""

from lib import roofline


def read(facts, what):
    trace = facts.get("trace")
    if facts.get("rehearsal"):  # XLA:CPU threads are no device: no number
        return None
    if not trace or not trace.get("busy_in_jobs_chip_s") \
            or not facts.get("rows_in"):
        return None
    busy = trace["busy_in_jobs_chip_s"]
    if what == "ms_per_Mrow":
        return busy * 1e3 / (facts["rows_in"] / 1e6)
    if what == "roofline":
        n_bytes = roofline.job_bytes(facts["rows_in"], facts["rows_out"],
                                     facts["key_bytes"])
        return 100.0 * roofline.least_seconds(
            n_bytes, facts["device_kind"]) / busy
    raise ValueError(what)
