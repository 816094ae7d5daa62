"""Percent of ``indirect_ms`` that the stage's least indirect traffic
would take at the chips' HBM peak: ``min_work["indirect_bytes"]`` (the
values, the indices and the gathered entries, each read once) over
peak bytes/s, over the device time under ``omp.indirect.*``; nothing
where either is missing."""
from bench.metrics import indirect_ms


def read(r):
    ms = indirect_ms.read(r)
    if ms is None or "indirect_bytes" not in r.work:
        return None
    least_s = r.work["indirect_bytes"] / (r.peaks["hbm_bytes_per_s"]
                                          * r.chips)
    return 100.0 * least_s / (1e-3 * ms)
