"""Milliseconds a committed round of the loop's time under the program's
`net:codec` label (`tracing.nested`, family `network`): the message codec
where the network family owned it: a request's decode and its reply's encode
in `RpcServer._dispatch`, a response's decode in `PeerLink.run` and
`PeerClient._read_loop`.
An estimate from the stretches the loop account keeps (`loop_account`). A
site's label has a row in every stretch that ran it, however small, so the
three labels, the family's other owners and its `rest` row sum to
`loop.network_ms_per_round`. None where no kept stretch in the window holds
the label: a program that does not split the network family."""

from chipbench.readers import loop_account

OWNER = "net:codec"


def read(obs):
    acct = loop_account.account(obs)
    if acct is None:
        return None
    row = next((r for (owner, _), r in acct.owners.items() if owner == OWNER), None)
    return None if row is None else loop_account.ms_per_round(obs, acct, row[1])
