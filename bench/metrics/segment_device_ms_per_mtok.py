"""segment_device_ms_per_mtok (ms/Mtok): device time of the segment
program (map, local reduce, owner lookup, bucketize, push, fold; the steal
claim and fetch where stealing is on) per million input tokens, averaged
over the cell's devices."""


def read(run):
    tokens = run.tokens_per_job * len(run.jobs)
    return run.trace.program_s("segment") * 1e3 / (tokens / 1e6)
