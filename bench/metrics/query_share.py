"""query_share (%): the device time under the program's
``fedzo.query`` scope, the loss queries (unflatten plus the model's
forward) inside the local phase, over chips x the traced window.
Empty for a program that compiles no named scopes."""
from scope_reduce import scope_share

SCOPE = "fedzo.query"


def read(ctx):
    return scope_share(ctx, SCOPE)
