"""work_imbalance (ratio): max over mean of the compute-repeats each rank
executed, summed over the traced jobs (``JobResult.work_per_rank``: with
stealing on, the engine's progress row, so a stolen task counts for the
rank that ran it; a task computed once counts 1). 1 is even work. Nothing
to read with a single rank."""
import numpy as np


def read(run):
    work = np.sum([j.work_per_rank for j in run.jobs], axis=0)
    if work.size < 2:
        return None
    return float(work.max() / work.mean())
