"""ingest_host_ms: the median host time of ``FleetService.ingest``,
from the round's start until ``ingest`` returns (the fetch, the copy of the
window's rates to the device, and the dispatch of the step), read from the
benchmark's own ``bench.ingest`` span."""
import statistics


def read(run):
    spans = run.spans.get("bench.ingest")
    return 1e3 * statistics.median(spans) if spans else None
