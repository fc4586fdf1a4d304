"""Host milliseconds the caller waits, after the device has answered,
for the native racer to land its cross-check verdict, per register
check: the program's ``racer.wait`` spans over the window, summed and
divided by the register checks. Nothing when the program records no
such span."""

#: names of the spans read
NAMES = {"racer.wait"}


def read(obs):
    ns = [s["dur"] for s in obs.get("spans") or ()
          if s.get("ph") == "X" and s["name"] in NAMES]
    if not ns or not obs.get("checks"):
        return None
    return sum(ns) / 1e6 / obs["checks"]
