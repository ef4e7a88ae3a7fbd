"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, read in
the worker after the window and before the reference runs."""


def read(facts, entry):
    peak = facts["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
