"""compact_roofline (%): the log compaction's share of its HBM roofline.

The least time is the log's bytes read once and written once: 12 B a
slot (word, document, count), one slot per input token, so 24 B per
token of the job, counted from the job and not from program shapes, over
the chips' summed HBM bandwidth from ``bench/peaks.json``. The share is
that over ``compact_ms_per_job``. None where that has nothing to read.
An unknown ``device_kind`` is an error."""
from bench import cells

BYTES_PER_TOKEN = 24


def least_seconds(tokens: int, chips: int, device_kind: str) -> float:
    """Seconds the chips need to read and write a log of ``tokens``
    12-byte slots."""
    bw = float(cells.peaks(device_kind)["hbm_bytes_per_s"])
    return BYTES_PER_TOKEN * tokens / (chips * bw)


def read(run):
    ms = cells.metric_reader("compact_ms_per_job")(run)
    if not ms:
        return None
    least = least_seconds(run.tokens_per_job, run.chips, run.device_kind)
    return 100.0 * least / (ms * 1e-3)
