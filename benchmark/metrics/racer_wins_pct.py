"""Share of register checks whose verdict the native C++ oracle, racing
the device, decided first (``RACE_STATS`` ``native_wins`` over the
window). Nothing when no check raced."""


def read(obs):
    race, n = obs.get("race"), obs.get("checks")
    if not race or not n or race["native_wins"] + race["tpu_wins"] == 0:
        return None
    return 100.0 * race["native_wins"] / n
