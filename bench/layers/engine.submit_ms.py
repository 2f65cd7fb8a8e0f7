"""engine.submit_ms: host milliseconds per batch inside ``Engine.submit``
(bucketing, rotation, LUT build and its host copy for the LUT cache,
dispatch), from the benchmark's own span around the call."""


def read(run, reduced):
    v = run.values
    spans = v.get("span_s", {})
    if not v.get("batches") or "bench.submit" not in spans:
        return None
    return 1e3 * spans["bench.submit"] / v["batches"]
