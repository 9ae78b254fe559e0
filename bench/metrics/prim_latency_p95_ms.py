"""95th percentile of client submit to result in hand, over every request
sent in the window (exact, not a histogram).  Host clock."""
import harness


def read(run):
    if not run.latencies_s:
        return None
    return 1e3 * harness.exact_percentile(run.latencies_s, 95)
