"""Host milliseconds of preparing register checks, per check: the
program's ``prep.*`` spans over the window (``prep.split`` of a keyed
history into keys, and per key ``prep.history``, ``prep.sentry``,
``prep.encode`` and ``prep.steps``), summed and divided by the register
checks. Nothing when the program records no such span."""

#: prefix of the span names read
PREFIX = "prep."


def read(obs):
    ns = [s["dur"] for s in obs.get("spans") or ()
          if s.get("ph") == "X" and s["name"].startswith(PREFIX)]
    if not ns or not obs.get("checks"):
        return None
    return sum(ns) / 1e6 / obs["checks"]
