"""segment_local_reduce_ms_per_mtok (ms/Mtok): device self time of the
segment program's ops under the scope ``local_reduce`` (each task's sort
and duplicate sum) per million input tokens, averaged over the devices
(``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.segment_ms_per_mtok(run, "local_reduce")
