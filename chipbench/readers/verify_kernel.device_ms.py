"""Device time of one msm_accumulate_kernel program, from the trace."""


def read(obs):
    k = (obs.get("trace") or {}).get("kernels", {}).get("msm_accumulate_kernel")
    return 1000.0 * k["seconds"] / k["events"] if k and k["events"] else None
