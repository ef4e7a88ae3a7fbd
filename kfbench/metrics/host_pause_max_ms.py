"""The longest stretch of the window in which a thread that only sleeps
five milliseconds at a time did not get to run: the process or the
machine stood still, or another thread held the interpreter (a garbage
collection does).  A slow block without such a pause beside it was the
loop waiting on the device or its runtime (``lib/hostwatch.py``)."""


def read(facts, entry):
    host = facts["train"].get("host")
    if host is None:
        return None
    return 1e3 * max((s for _, s in host["pauses"]), default=0.0)
