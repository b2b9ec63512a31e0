"""Times the device answer was not taken as is, window and drain; non-zero voids the run."""


def read(obs):
    v = obs["whole"]["verifier"]
    return float(sum(v.get(k, 0) for k in ("msm_redispatch", "group_solo_redispatch", "group_host_verify")))
