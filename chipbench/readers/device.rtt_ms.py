"""A tiny jitted op dispatched and read back: median of 20, after warm-up, before the window."""


def read(obs):
    return obs["rtt_ms"]
