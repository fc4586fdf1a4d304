"""Device-to-host fetches that paid a round trip (``LAUNCH_STATS``
``host_syncs``, counted in ``wgl_bitset._host_get``) over the window,
per register check."""


def read(obs):
    n = obs.get("checks")
    if not n or "launch" not in obs:
        return None
    return obs["launch"]["host_syncs"] / n
