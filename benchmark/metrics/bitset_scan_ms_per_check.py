"""Device milliseconds of the bitset kernels (the ``_bitset_scan``
Pallas kernel, alone or chained) per check, summed from the trace's
device time of every op whose name holds one of ``KERNEL``. A check is
one register's check: per history for one register, per key for a
multi-key history. Nothing when no bitset kernel ran."""

#: substrings of the kernel's device op names
KERNEL = ["bitset"]


def read(obs):
    t = obs.get("trace")
    if t is None or not obs.get("checks"):
        return None
    s = sum(v for name, v in t["op_s"].items()
            if any(k in name for k in KERNEL))
    return 1e3 * s / obs["checks"] if s > 0 else None
