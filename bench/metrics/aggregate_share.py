"""aggregate_share (%): the device time under the program's
``fedzo.aggregate`` scope, from client deltas to new params (the mean or
AirComp with its reduce kernel and noise walk, the mesh psum, server
momentum), over chips x the traced window.
Empty for a program that compiles no named scopes."""
from scope_reduce import scope_share

SCOPE = "fedzo.aggregate"


def read(ctx):
    return scope_share(ctx, SCOPE)
