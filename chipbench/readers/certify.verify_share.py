"""Share of the certify stage spent inside the verifier stages: over the
headers certified in the window, the union of the `stage` intervals
[t_in, t_forwarded] about that header on every validator, clipped to the
header's certify span, summed, over the summed spans. The rest is certify's
own time (the network, the core, the loop)."""

import collections

from chipbench.readers import flight_window as fw


def read(obs):
    win = fw.window(obs)
    certified = fw.within(win, "certify", "t1")
    if not certified:
        return None
    about = collections.defaultdict(list)
    for s in win.by["stage"]:
        about[s.key].append((s.t_in, s.t_forwarded))
    inside = sum(fw.length(fw.union(about[c.key], c.t0, c.t1)) for c in certified)
    spans = sum(c.t1 - c.t0 for c in certified)
    return 100.0 * inside / spans if spans > 0 else None
