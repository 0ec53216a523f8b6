"""feed_sync_share (%): segments the feed built synchronously, in the
caller's thread, over all it built (``FeedStats.prefetch_misses /
segments_built``), over the traced jobs."""


def read(run):
    built = sum(j.segments for j in run.jobs)
    return 100.0 * sum(j.prefetch_misses for j in run.jobs) / built
