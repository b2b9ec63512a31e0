"""Share of the loop's time inside callbacks during which its thread stood
off a core: Σ (`busy_s` - `cpu_s`) ÷ Σ `busy_s`, what handing the interpreter
over costs the loop. `cpu_s` is the thread's, so what the loop itself burns
between callbacks counts against it: a small negative reading is that.
An estimate from the stretches the account keeps (`loop_account`): two
readings compare only at one `tracing.ACCOUNT_KEEP_S` and `ACCOUNT_REST_S`."""

from chipbench.readers import loop_account


def read(obs):
    acct = loop_account.account(obs)
    return 100.0 * acct.offcpu / acct.busy if acct is not None and acct.busy else None
