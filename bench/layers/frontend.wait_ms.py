"""frontend.wait_ms: mean admission-queue wait of the window's requests,
``Ticket.waited_ms`` as the program's queue stamps it (serve/queue.py)."""


def read(run, reduced):
    waits = run.values.get("wait_ms")
    if not waits:
        return None
    return sum(waits) / len(waits)
