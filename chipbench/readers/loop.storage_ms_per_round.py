"""Milliseconds a committed round of the loop's callbacks owned by the
`storage` family (`narwhal_tpu.tracing.OWNER_FAMILIES`).
An estimate from the stretches the account keeps (`loop_account`): two
readings compare only at one `tracing.ACCOUNT_KEEP_S` and `ACCOUNT_REST_S`."""

from chipbench.readers import loop_account


def read(obs):
    return loop_account.family_ms_per_round(obs, "storage")
