"""Host milliseconds of launching register checks on the device and
waiting for their answers, per check: the program's ``launch``,
``device_wait`` (polling the device while the native racer runs) and
``host_sync`` (the device-to-host fetch) spans over the window, summed
and divided by the register checks. Nothing when the program records
no such span."""

#: names of the spans read
NAMES = {"launch", "device_wait", "host_sync"}


def read(obs):
    ns = [s["dur"] for s in obs.get("spans") or ()
          if s.get("ph") == "X" and s["name"] in NAMES]
    if not ns or not obs.get("checks"):
        return None
    return sum(ns) / 1e6 / obs["checks"]
