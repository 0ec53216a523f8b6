"""feed_wait_ms_per_job (ms): time the step loop waits for each segment's
input, per traced job: the span ``mr.feed.wait`` around
``SegmentFeed.next_segment`` (a synchronous build, or the wait on the
prefetch), summed over the job's segments (``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.host_ms_per_job(run, "mr.feed.wait")
