"""cohort_share (%): the device time under the program's
``fedzo.cohort`` scope, the round's realization (participants,
minibatches, size weights, fault and channel draws), over chips x the
traced window.
Empty for a program that compiles no named scopes."""
from scope_reduce import scope_share

SCOPE = "fedzo.cohort"


def read(ctx):
    return scope_share(ctx, SCOPE)
