"""apply_p95_ms: 95th percentile of one scoring request's wall, host
array in to host labels out, over every completed request of the window."""

from benchmark.harness.stats import percentile


def value(run) -> float:
    walls = [s.end - s.start for s in run.completed]
    run.say(f"apply_p95_ms is over {len(walls)} requests")
    return 1e3 * percentile(walls, 95)
