"""steal_ms_per_mtok (ms/Mtok): device self time of the segment program's
ops under the scopes ``claim`` (the shared claim over the progress row
and its psum updates) and ``fetch`` (the all-to-all that ships each
claimed task's input to its executor) per million input tokens, averaged
over the devices (``bench/spans.py``). Nothing to read where stealing is
off."""
from bench import spans


def read(run):
    return spans.segment_ms_per_mtok(run, "claim", "fetch")
