"""Device: peak bytes in use on the fullest chip, from the allocator
(``memory_stats()["peak_bytes_in_use"]``), in GB.  Bounds ``chunk_rows``,
and an OOM backoff halves it."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
