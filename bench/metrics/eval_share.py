"""eval_share (%): the device time under the program's ``fedzo.eval``
scope, the in-scan eval, over chips x the traced window.
Empty for a program that compiles no named scopes."""
from scope_reduce import scope_share

SCOPE = "fedzo.eval"


def read(ctx):
    return scope_share(ctx, SCOPE)
