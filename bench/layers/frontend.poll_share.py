"""frontend.poll_share: share of the window the host spends inside
``Frontend.poll`` (batching, Engine submit and collect), in %, from the
benchmark's span around each call."""


def read(run, reduced):
    v = run.values
    if "poll_s" not in v:
        return None
    return 100.0 * v["poll_s"] / v["window_s"]
