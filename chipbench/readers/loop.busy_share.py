"""Share of the window the committee's event loop spent inside the callbacks
it ran (the loop account's `busy_s`); the rest it waited in its selector.
An estimate from the stretches the account keeps (`loop_account`): two
readings compare only at one `tracing.ACCOUNT_KEEP_S` and `ACCOUNT_REST_S`."""

from chipbench.readers import loop_account


def read(obs):
    acct = loop_account.account(obs)
    return None if acct is None else 100.0 * acct.busy / acct.covered
