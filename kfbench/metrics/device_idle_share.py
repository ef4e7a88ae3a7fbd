"""1 - (union of the device's operation intervals) / traced window,
averaged over the chips, in percent."""


def read(facts, entry):
    red = (facts.get("trace") or {}).get("reduced")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
