"""unscoped_share (%): the device time under none of the program's named
scopes, the scan's bookkeeping (key splits, ring writes) and the copies
XLA inserts, over chips x the traced window. A refactor that drops a
scope shows here. With cohort_share, local_phase_share, aggregate_share
and eval_share it sums to 100 - device_idle_share. Empty for a program
that compiles no named scopes."""
from scope_reduce import UNSCOPED, scope_share


def read(ctx):
    return scope_share(ctx, UNSCOPED)
