"""50th percentile (nearest rank) of the latency of every request due
in the window, from its due time to its answer; a refused request counts
as never answered."""
from harness import latencies_s, percentile


def read(run):
    lat = latencies_s(run)
    return 1e3 * percentile(lat, 50) if len(lat) else None
