"""finish_device_ms_per_job (ms): device time of the finish program (drain
of the last chunk, ``combine_records``, the ``tree_combine`` merge tree)
per job, averaged over the cell's devices."""


def read(run):
    return run.trace.program_s("finish") * 1e3 / len(run.jobs)
