"""99th percentile of every gap between consecutive output tokens of a
request, both inside the window. A prefill stalls every running request,
so the gaps that span one count, and p99 lies among them: the stall a
user sees. (A 95th percentile sat on the edge between those gaps and
plain decode steps, and moved between them from run to run.) A window
holds thousands of gaps, so ten or more lie beyond p99."""
from chipbench.record import percentile


def read(run):
    gaps = [b - a for ts in run.times.values() for a, b in zip(ts, ts[1:])
            if run.w0 <= a and b <= run.w1]
    p = percentile(gaps, 99)
    return None if p is None else 1e3 * p
