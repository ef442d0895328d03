"""The traced run's ring goodput over the plain control measured in the
same process right after the window: the same chunks through
jax.device_put -> ready -> np.asarray, as many in flight, no ring, no
framing, no kernel, no checks. What the fabric's host side costs against
the bare host link."""
LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "ratio"
MOVES = "goodput_gbps"
SOURCE = "host_clock"


def read(obs):
    raw = obs.get("raw_link_gbps")
    if not raw:
        return None
    return obs["end_to_end"]["goodput_gbps"] / raw
