"""local_phase_share (%): the device time under the program's
``fedzo.local`` scope, the M vmapped local phases of a round with their
ZO kernels and loss queries, over chips x the traced window.
Empty for a program that compiles no named scopes."""
from scope_reduce import scope_share

SCOPE = "fedzo.local"


def read(ctx):
    return scope_share(ctx, SCOPE)
