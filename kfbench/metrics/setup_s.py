"""Process start to the window's start, compilation included: what every
run of every check pays before it measures anything."""


def read(facts, entry):
    return facts["window_wall"] - facts["t_start"]
