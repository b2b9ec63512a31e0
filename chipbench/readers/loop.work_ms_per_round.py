"""Milliseconds of its own callbacks the committee's event loop ran per
committed round: the loop account's `busy_s` over the window's rounds.
An estimate from the stretches the account keeps (`loop_account`): two
readings compare only at one `tracing.ACCOUNT_KEEP_S` and `ACCOUNT_REST_S`."""

from chipbench.readers import loop_account


def read(obs):
    acct = loop_account.account(obs)
    return None if acct is None else loop_account.ms_per_round(obs, acct, acct.busy)
