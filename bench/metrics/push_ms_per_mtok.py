"""push_ms_per_mtok (ms/Mtok): device self time of the segment program's
ops under the scope ``push`` (the all-to-all pair that sends each task's
key and value buckets to their owners) per million input tokens,
averaged over the devices (``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.segment_ms_per_mtok(run, "push")
