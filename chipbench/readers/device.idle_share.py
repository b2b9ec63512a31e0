"""1 - union of device-operation intervals over the traced slice, in per cent."""


def read(obs):
    t = obs.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / obs["trace_window_s"]) if t and obs["trace_window_s"] > 0 else None
