"""Memory-roofline share of the verify kernel: least time for a bucket's bytes over its device time."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_work", os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "work.py")
)
work = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(work)


def read(obs):
    k = (obs.get("trace") or {}).get("kernels", {}).get("msm_accumulate_kernel")
    if not k or not k["events"] or not k["seconds"] or "peaks" not in obs:
        return None
    least = work.least_seconds(obs["verify_bucket"], obs["peaks"])
    return 100.0 * least / (k["seconds"] / k["events"])
