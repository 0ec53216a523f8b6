"""segment_roofline (%): the segment program's share of its HBM roofline.

The least time is the input's bytes (4 B per token, counted from the job,
not from program shapes) over the chips' summed HBM bandwidth from
``bench/peaks.json``; the share is that over the segment program's device
time, averaged over the devices. An unknown ``device_kind`` is an error."""
from bench import cells

BYTES_PER_TOKEN = 4


def least_seconds(tokens: int, chips: int, device_kind: str) -> float:
    """Seconds the chips need to read ``tokens`` int32 tokens from HBM."""
    bw = float(cells.peaks(device_kind)["hbm_bytes_per_s"])
    return BYTES_PER_TOKEN * tokens / (chips * bw)


def read(run):
    tokens = run.tokens_per_job * len(run.jobs)
    least = least_seconds(tokens, run.chips, run.device_kind)
    return 100.0 * least / run.trace.program_s("segment")
