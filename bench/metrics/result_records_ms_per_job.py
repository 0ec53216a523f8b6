"""result_records_ms_per_job (ms): time ``JobHandle.result()`` spends on the
host alone once the copies are done, per traced job: the span
``mr.result.records`` (the records dict and the use case's
``finalize``; ``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.host_ms_per_job(run, "mr.result.records")
