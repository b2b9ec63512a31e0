"""Milliseconds a committed round of the loop's time in the connection
drainers' own Python (`FrameSender._drain_loop`: taking the queued frames,
packing headers, the counters, the task's turns), after the seals and the
writes come off it as `net:aead` and `net:write`. An estimate from the
stretches the loop account keeps (`loop_account`). None where no kept
stretch in the window holds the drainer, and None on a program without the
`net:write` label, whose drainer still holds its writes and seals."""

from chipbench.readers import loop_account

OWNER = "narwhal_tpu/network/rpc.py:FrameSender._drain_loop"


def read(obs):
    acct = loop_account.account(obs)
    if acct is None:
        return None
    owners = {owner: r for (owner, _), r in acct.owners.items()}
    if OWNER not in owners or "net:write" not in owners:
        return None
    return loop_account.ms_per_round(obs, acct, owners[OWNER][1])
