"""Reader kind ``profile``: a named reduction of the profiler trace of
the traced slice (``yardstick/trace_reduce.py`` made ``run["profile"]``).

    "source": {"kind": "profile", "reduction": "kernel_ms"}

kernel_ms              mean device time of one execution of a verify program
                       (the executions that lie wholly inside the slice)
kernel_roofline_share  the least time the chip could take for the slice's
                       mean launch (``yardstick/roofline.py``) over kernel_ms
device_idle_share      1 - union of device-busy intervals over the slice
"""

from __future__ import annotations

from yardstick import roofline


def read(source: dict, run: dict):
    profile = run.get("profile")
    if not profile:
        return None
    what = source["reduction"]
    if what == "device_idle_share":
        if not profile.get("window_s"):
            return None
        return 100.0 * (1.0 - profile["busy_s"] / profile["window_s"])
    kernel_s = profile.get("program_mean_s")
    if not kernel_s:
        return None
    if what == "kernel_ms":
        return kernel_s * 1e3
    if what == "kernel_roofline_share":
        if not profile.get("sigs_per_launch"):
            return None
        least = roofline.least_time_s(
            run["config"]["route"], profile["sigs_per_launch"],
            run["device"]["kind"])
        return 100.0 * least["seconds"] / kernel_s
    raise ValueError(f"unknown profile reduction {what!r}")
