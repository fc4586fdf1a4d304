"""Share of the window in which no operation ran on the chip, from the
profiler trace (1 - union of device-op intervals / window), in the
analyze cells."""


def read(obs):
    t = obs.get("trace")
    return None if t is None else t["idle_pct"]
