"""result_host_ms_per_job (ms): the part of ``JobHandle.result()`` in which
the device runs no operation, per traced job, averaged over the cell's
devices: the records' copy to the host and the building of the records
dict. The rest of the ``bench.result`` span is the device finishing the
segment programs still queued and the finish program, which other metrics
read."""


def read(run):
    return 1e3 * run.trace.idle_in_s("bench.result") / len(run.jobs)
