"""The device a run is made on: found once, never substituted."""
import os
import sys


def find_devices(chips: int, rehearsal: bool) -> list:
    """The devices the cell runs on, or SystemExit: the one place that
    decides whether a run may proceed."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
    try:
        import jax

        devices = jax.devices()
    except Exception as e:  # jax raises RuntimeError subclasses of its own
        sys.exit(f"benchmark: jax found no device: {e}")
    platform = devices[0].platform
    if rehearsal != (platform == "cpu"):
        sys.exit(f"benchmark: platform is {platform!r}: a measured run "
                 "needs an accelerator and only --rehearsal 1 may run on "
                 "the cpu (and runs nowhere else); no result")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chips, jax reports "
                 f"{len(devices)}; no result")
    return devices


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
