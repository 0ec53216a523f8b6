"""result_fetch_ms_per_job (ms): time ``JobHandle.result()`` spends copying
the finished job from the device to the host, per traced job: the span
``mr.result.fetch`` (records, overflow count, owner map, progress rows),
after the device has drained (``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.host_ms_per_job(run, "mr.result.fetch")
